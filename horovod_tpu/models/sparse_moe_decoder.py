"""Decoder with grouped-KV attention behind a learned sparse indexer and a
dropless mixture of gated experts (the language model of
Keye-VL-2.0-30B-A3B; docs/sparse_attention.md, docs/moe.md).

A second block beside ``models/gpt.py``, which is not stretched to hold
it: RMSNorm, rotary position over the whole head, grouped KV heads with a
per-head RMSNorm on q and k, :func:`hvd.sparse_attention` (every query
attends the ``topk`` keys its indexer scores highest), top-k routed SiLU
experts through :func:`hvd.moe_ffn_dropless` (told which experts this chip
holds), an untied head. No bias anywhere. Driven by the published
``config.json`` key names (:meth:`SparseMoEConfig.from_dict`).

bfloat16 activations and matmul operands with float32 accumulation;
float32 parameters, norms, rotary angles, softmax statistics and router
probabilities. Each block is rematerialised in the backward pass and keeps
two named values of ``hvd.sparse_attention`` (``jax.checkpoint`` with
``save_only_these_names``): the forward kernel's output and log-sum-exp
rows (0.14 GB a layer at T = 16k, against 18 ms to redo them) and the
selection at one bit a pair (``T * T / 8`` bytes: 34 MB, against 7 ms of
index kernel; its int8 mask, 0.27 GB, is not kept). The recomputed forward
then runs neither kernel, nor the indexer's projections, which feed nothing
but the selection.

Initial weights: normal(``initializer_range``) for every matrix and the
embedding, ones for every RMSNorm scale (the family's convention).

The vision tower is not built: on text tokens the three ``mrope_section``
position ids coincide and the rotary embedding is the ordinary one. The
indexer's own training loss (DeepSeek-V3.2's KL against head-summed
attention probabilities) is not built either: under the language-model
loss alone the indexer's weights receive a zero gradient (ROADMAP R0).
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..moe.layer import moe_ffn_dropless
from ..ops.sparse_attention import (OUT_NAME, SELECTION_NAME,
                                    sparse_attention)


@dataclass(frozen=True)
class SparseMoEConfig:
    vocab_size: int = 151936
    layers: int = 48                  # the depth built
    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    num_experts: int = 128            # the router's width
    num_local_experts: int = 128      # experts held here ...
    first_local_expert: int = 0       # ... starting at this one
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048
    initializer_range: float = 0.02

    dtype: jnp.dtype = jnp.bfloat16
    return_hidden: bool = False

    @classmethod
    def from_dict(cls, cfg: dict, **overrides) -> "SparseMoEConfig":
        """From a ``config.json`` as published (``sa_config`` nested);
        ``layers`` is the depth to build where given, else
        ``num_hidden_layers``."""
        sa = cfg["sa_config"]
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("the indexer is built for one key head")
        flat = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        flat.setdefault("layers", cfg.get("num_hidden_layers"))
        flat.update(indexer_num_heads=sa["indexer_num_heads"],
                    indexer_head_dim=sa["indexer_head_dim"],
                    topk=sa["topk"], rope_theta=float(cfg["rope_theta"]))
        flat.update(overrides)
        return cls(**flat)


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope(x, theta: float):
    """Rotary embedding over the last dim of x [B, T, heads, D], positions
    0..T-1, halves rotated; float32 angles."""
    T, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


class _Scale(nn.Module):
    """RMSNorm over the last dim with a learned scale."""
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return rms_norm(x, scale, self.eps)


class _Indexer(nn.Module):
    cfg: SparseMoEConfig

    @nn.compact
    def __call__(self, u):
        """(index_q [B, T, Hi, Di], index_k [B, T, Di], index_w [B, T, Hi])
        of the normed block input; no gradient comes back."""
        cfg = self.cfg
        B, T, d = u.shape
        Hi, Di = cfg.indexer_num_heads, cfg.indexer_head_dim
        init = nn.initializers.normal(cfg.initializer_range)
        u = lax.stop_gradient(u)

        def proj(name, n):
            w = self.param(name, init, (d, n), jnp.float32)
            return u @ lax.stop_gradient(w).astype(cfg.dtype)

        qi = rope(proj("wq", Hi * Di).reshape(B, T, Hi, Di), cfg.rope_theta)
        ki = rope(proj("wk", Di)[:, :, None, :], cfg.rope_theta)[:, :, 0]
        return qi, ki, proj("ww", Hi).astype(jnp.float32)


class _Attention(nn.Module):
    cfg: SparseMoEConfig

    @nn.compact
    def __call__(self, u, index):
        cfg = self.cfg
        B, T, d = u.shape
        H, Hk, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        init = nn.initializers.normal(cfg.initializer_range)

        def w(name, *shape):
            return self.param(name, init, shape, jnp.float32).astype(
                cfg.dtype)

        def head_norm(name, x):
            scale = self.param(name, nn.initializers.ones, (D,), jnp.float32)
            return rms_norm(x, scale, cfg.rms_norm_eps)

        q = head_norm("q_norm", (u @ w("wq", d, H * D)).reshape(B, T, H, D))
        k = head_norm("k_norm", (u @ w("wk", d, Hk * D)).reshape(B, T, Hk, D))
        v = (u @ w("wv", d, Hk * D)).reshape(B, T, Hk, D)
        o = sparse_attention(rope(q, cfg.rope_theta), rope(k, cfg.rope_theta),
                             v, *index, topk=cfg.topk)
        return o.reshape(B, T, H * D) @ w("wo", H * D, d)


class _MoE(nn.Module):
    cfg: SparseMoEConfig

    @nn.compact
    def __call__(self, z):
        cfg = self.cfg
        B, T, d = z.shape
        held, f = cfg.num_local_experts, cfg.moe_intermediate_size
        init = nn.initializers.normal(cfg.initializer_range)
        params = {
            "router": self.param("router", init, (d, cfg.num_experts),
                                 jnp.float32),
            "w1": self.param("w1", init, (held, d, f), jnp.float32),
            "w3": self.param("w3", init, (held, d, f), jnp.float32),
            "w2": self.param("w2", init, (held, f, d), jnp.float32),
        }
        y, aux = moe_ffn_dropless(
            z.reshape(B * T, d), params,
            experts_per_token=cfg.num_experts_per_tok,
            first_expert=cfg.first_local_expert)
        self.sow("intermediates", "moe_expert_load", aux.load)
        return y.reshape(B, T, d)


class _Block(nn.Module):
    cfg: SparseMoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        u = _Scale(cfg.rms_norm_eps, name="ln1")(x)
        index = _Indexer(cfg, name="indexer")(u)
        h = x + _Attention(cfg, name="attn")(u, index)
        return h + _MoE(cfg, name="moe")(
            _Scale(cfg.rms_norm_eps, name="ln2")(h))


class SparseMoEDecoder(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32, or the final
    normed hidden states [B, T, d] with ``cfg.return_hidden`` (for
    ``hvd.lm_head_loss(h, params["head"], labels)``: the head is untied)."""
    cfg: SparseMoEConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        init = nn.initializers.normal(cfg.initializer_range)
        embed = self.param("embed", init,
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        head = self.param("head", init,
                          (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = embed.astype(cfg.dtype)[tokens]
        block = nn.remat(
            _Block, policy=jax.checkpoint_policies.save_only_these_names(
                OUT_NAME, SELECTION_NAME))
        for i in range(cfg.layers):
            x = block(cfg, name=f"h{i}")(x)
        x = _Scale(cfg.rms_norm_eps, name="ln_f")(x)
        if cfg.return_hidden:
            return x
        return jnp.einsum("btc,vc->btv", x, head.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)
