"""A decoder-hybrid-decoder language model (``model_type`` ``phi4flash``;
the architecture's paper, arXiv 2507.06607, calls it SambaY): state-space
layers, differential attention with a window or over every key, and a
second half whose layers READ what the first half made: gated memory units
read the last state-space layer's scan output ``m``, cross-attention layers
read the full-attention layer's K and V (docs/selective_scan.md,
docs/flash_window.md).

A module beside ``sparse_moe_decoder.py`` and not new kinds of its block:
that block is RMSNorm, rotary position, per-head q / k norms and an untied
head around attention and experts, and none of it is here. What the two
share is imported, not copied: the call of the flash kernels
(``causal_attention``), the gated MLP (``_GatedMLP``), the rule for what a
rematerialised block keeps (``kept_within`` with its value names) and
GPT-2's LayerNorm (``gpt._layer_norm``).

With ``u = LayerNorm(x)`` (weight and bias), every layer is
``h = x + Mixer(u)``, ``y = h + W2 (silu(W1 z) * W3 z)``,
``z = LayerNorm(h)``; a final LayerNorm; the head is the embedding's
transpose; no positional embedding. A layer's kind comes from
``SambaYConfig.layer_types`` and ``l`` is its PUBLISHED index
(``SambaYConfig.layers``), which the differential attention's ``lam0``
reads:

* ``mamba``: ``[xc | z] = u W_in``; ``xs = silu(conv1d_causal(xc) + b_c)``
  (depthwise, ``mamba_d_conv`` taps); ``[dt | B | C] = xs W_x``;
  ``D_t = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``m = hvd.selective_scan(xs, D_t, A, B, C, Dskip)`` in float32; out
  ``= (m * silu(z)) W_out``. Scope ``hvd.ssm`` with ``hvd.selective_scan``
  inside. The last such layer before a gated memory unit hands on ``m``.
* ``sliding_attention`` / ``full_attention``: ``[q | k | v] = u W_qkv``;
  the heads pair up, ``(2p, 2p + 1)``; with ``P1 = softmax(q1 k1^T / 8)``,
  ``P2 = softmax(q2 k2^T / 8)`` under the causal (and window) mask,
  ``a = P1 [v1 | v2] - lam * P2 [v1 | v2]``,
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``,
  ``lam0 = 0.8 - 0.6 exp(-0.3 l)``, ``o = RMSNorm_128(a) * (1 - lam0)``;
  out ``= concat_p(o) W_out``. The last full layer before a
  cross-attention layer hands on its ``k`` and ``v``.
* ``cross_attention``: ``q = u W_q`` only, ``k`` and ``v`` handed on; the
  same differential form with its own ``lam`` and norm; causal, full.
* ``gmu``: out ``= (silu(u W_in) * m) W_out``.

The two softmax maps of a pair are ONE flash call at head width 128, the
kernels the other families run: KV pair ``j``'s ``[k1 | k2]`` and
``[v1 | v2]`` are 128-wide KV heads as the projection lays them out, and
query head ``2p + e`` is its 64 values in half ``e`` of a 128-wide head with
zeros in the other half, so ``q . [k1 | k2]`` is ``q1 . k1`` (``e = 0``) or
``q2 . k2`` (``e = 1``) and the head's output is ``P_e [v1 | v2]``: 40
query heads on 10 KV heads, scale 1/8. The zeros cost no matmul time on a
128-wide MXU and 84 MB of q at 8k. The two elementwise halves around that
call, the query into its half and the pair's difference with its 128-wide
norm, are ``ops/diff_attention.py``'s ``lay_in_halves`` and
``diff_combine``: one pass each way over ``[B, T, H * 128]`` as it lies.

Each block is rematerialised in the backward pass and takes ``m``, ``k``
and ``v`` as extra inputs and outputs: a block's inputs are kept, so what
is handed on is kept once, whatever the number of its readers, and their
gradients come back summed. Kept besides: the flash kernels' and the scan's
outputs (the recomputed forward runs neither) and, by ``kept_within``'s
budget, the mixers' projections, each kind's by every layer that has it or
by none, and the MLP's hidden projections (336 MB a layer at 8k) by as many
of the LAST layers as fit what the mixers' leave of the budget (2 of 6 at
the cell's size); the layers before them make theirs again, rematerialised
without that name (``rematerialised``).
Trace-time counters: ``shared.memory_readers``, ``shared.kv_readers``,
``remat.kept_bytes``, ``remat.kept_layers``, ``remat.kept_names``.

Initial weights: normal(``initializer_range``) for every matrix and the
embedding, LayerNorm at (1, 0), the convolution's taps and bias
uniform(+-``mamba_d_conv``^-0.5), ``A_log = log(1..N)``, ``Dskip = 1``,
``b_dt`` the inverse softplus of a log-uniform draw in [1e-3, 1e-1],
``lq*`` / ``lk*`` normal(0.1), the 128-wide norm's scale 1.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import diff_attention as _diff
from ..ops import flash_attention as _flash
from ..ops import selective_scan as _scan
from ..ops.embed_lookup import embed_lookup
from .gpt import _layer_norm
from .sparse_moe_decoder import (MLP_HIDDEN_NAME, QKV_NAME, _GatedMLP,
                                 causal_attention, kept_within,
                                 rematerialised)

MAMBA, SLIDING, FULL = "mamba", "sliding_attention", "full_attention"
GMU, CROSS = "gmu", "cross_attention"
KINDS = (MAMBA, SLIDING, FULL, GMU, CROSS)

# ``checkpoint_name``s of a block's values beside the decoder's own.
SSM_IN_NAME = "hvd_block_ssm_in"
GMU_IN_NAME = "hvd_block_gmu_in"


@dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 200064
    layers: Tuple[int, ...] = tuple(range(32))     # published indices built
    layer_types: Tuple[str, ...] = ()              # a kind a built layer
    hidden_size: int = 2560
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    initializer_range: float = 0.02
    dtype: jnp.dtype = jnp.bfloat16
    return_hidden: bool = False
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def hands_on(self, i: int) -> bool:
        """Built layer ``i`` is the last of its kind before a reader of
        what that kind makes (``mamba`` before ``gmu``, ``full_attention``
        before ``cross_attention``)."""
        reader = {MAMBA: GMU, FULL: CROSS}.get(self.layer_types[i])
        later = self.layer_types[i + 1:]
        if reader is None or reader not in later:
            return False
        return self.layer_types[i] not in later[:later.index(reader)]

    @classmethod
    def from_dict(cls, cfg: dict, **overrides) -> "SambaYConfig":
        """From a ``config.json`` as published plus ``layers`` (the
        published indices to build) and ``layer_types`` (their kinds);
        the state-space sizes by the family's convention where absent."""
        if cfg.get("model_type") != "phi4flash":
            raise ValueError("model_type is not phi4flash")
        flat = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        flat["layers"] = tuple(cfg["layers"])
        flat["layer_types"] = tuple(cfg["layer_types"])
        flat.setdefault("mamba_dt_rank", math.ceil(cfg["hidden_size"] / 16))
        flat.update(overrides)
        out = cls(**flat)
        out.validate()
        return out

    def validate(self) -> None:
        kinds = self.layer_types
        if len(kinds) != len(self.layers) or set(kinds) - set(KINDS):
            raise ValueError(f"layer_types {kinds} for layers {self.layers}")
        for i, kind in enumerate(kinds):
            source = {GMU: MAMBA, CROSS: FULL}.get(kind)
            if source is not None and source not in kinds[:i]:
                raise ValueError(f"layer {i} ({kind}) reads a {source} "
                                 f"layer's output and none stands before it")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("differential attention pairs the heads up")


def remat_candidates(cfg: SambaYConfig, B: int, T: int) -> dict:
    """``{name: bytes a layer}`` of what a block's backward would otherwise
    make again, dearest per byte first (sparse_moe_decoder.py
    ``remat_candidates``' form)."""
    row = B * T * jnp.dtype(cfg.dtype).itemsize
    H, Hk, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    qkv = {SLIDING: (H + 2 * Hk) * D, FULL: (H + 2 * Hk) * D, CROSS: H * D}
    each = {
        QKV_NAME: [row * qkv.get(k, 0) for k in cfg.layer_types],
        SSM_IN_NAME: [row * 2 * cfg.d_inner * (k == MAMBA)
                      for k in cfg.layer_types],
        GMU_IN_NAME: [row * cfg.d_inner * (k == GMU)
                      for k in cfg.layer_types],
        MLP_HIDDEN_NAME: [2 * row * cfg.intermediate_size] * len(cfg.layers),
    }
    return {name: tuple(by) for name, by in each.items() if any(by)}


def remat_kept_anyway(cfg: SambaYConfig, B: int, T: int) -> int:
    """Bytes the blocks keep whatever the budget says: a block's input; an
    attention layer's 128-wide output with a float32 log-sum-exp a head; a
    state-space layer's scan output and the states its chunks start from
    (at the default chunk); K and V where they are handed on."""
    n, item = B * T, jnp.dtype(cfg.dtype).itemsize
    H, Hk, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    total = 0
    for i, kind in enumerate(cfg.layer_types):
        total += n * cfg.hidden_size * item
        if kind in (SLIDING, FULL, CROSS):
            total += n * H * (2 * D * item + 4)
        if kind == MAMBA:
            total += n * cfg.d_inner * item + (
                n // _scan.DEFAULT_BLOCKS[0]) * cfg.d_inner * \
                cfg.mamba_d_state * 4
        if kind == FULL and cfg.hands_on(i):
            total += 2 * n * Hk * D * item
    return total


def remat_kept(cfg: SambaYConfig, B: int, T: int,
               memory_bytes: Optional[int] = None) -> dict:
    return kept_within(remat_candidates(cfg, B, T),
                       remat_kept_anyway(cfg, B, T), memory_bytes)


def _normal(std):
    return nn.initializers.normal(std)


def _uniform(bound):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a log-uniform draw in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


def causal_conv(x, w, b):
    """Depthwise causal convolution over time: x [B, T, C] (float32), w
    [taps, C], b [C]; tap ``taps - 1`` multiplies the token itself."""
    taps, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return b + sum(w[k] * padded[:, k:k + T] for k in range(taps))


class _Mamba(nn.Module):
    cfg: SambaYConfig

    @nn.compact
    def __call__(self, u):
        """(the mixer's output [B, T, d], the scan's output m [B, T, Dn])."""
        cfg, f32 = self.cfg, jnp.float32
        d, Dn, N, R = (cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state,
                       cfg.mamba_dt_rank)
        init = _normal(cfg.initializer_range)
        conv_init = _uniform(cfg.mamba_d_conv ** -0.5)

        def w(name, *shape):
            return self.param(name, init, shape, f32).astype(cfg.dtype)

        xz = checkpoint_name(u @ w("in_proj", d, 2 * Dn), SSM_IN_NAME)
        xc, z = jnp.split(xz, 2, axis=-1)
        xs = nn.silu(causal_conv(
            xc.astype(f32),
            self.param("conv_w", conv_init, (cfg.mamba_d_conv, Dn), f32),
            self.param("conv_b", conv_init, (Dn,), f32)))
        dbc = jnp.dot(xs.astype(cfg.dtype), w("x_proj", Dn, R + 2 * N),
                      preferred_element_type=f32)
        dt, Bm, Cm = jnp.split(dbc, (R, R + N), axis=-1)
        dt = jax.nn.softplus(
            jnp.dot(dt.astype(cfg.dtype), w("dt_proj", R, Dn),
                    preferred_element_type=f32)
            + self.param("dt_bias", _dt_bias_init, (Dn,), f32))
        A = -jnp.exp(self.param("A_log", _a_log_init, (Dn, N), f32))
        m = _scan.selective_scan(
            xs, dt, A, Bm, Cm,
            self.param("D", nn.initializers.ones, (Dn,), f32),
            out_dtype=cfg.dtype)
        gated = m * nn.silu(z.astype(f32)).astype(cfg.dtype)
        return gated @ w("out_proj", Dn, d), m


class _DiffAttention(nn.Module):
    """Differential attention of the built layer ``index``; with ``kv`` the
    keys and values are another layer's and only q is projected."""
    cfg: SambaYConfig
    index: int

    @nn.compact
    def __call__(self, u, kv=None):
        """(the mixer's output [B, T, d], (k, v) [B, T, Hk / 2, 128])."""
        cfg, f32 = self.cfg, jnp.float32
        B, T, d = u.shape
        H, Hk, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        init = _normal(cfg.initializer_range)
        kind = cfg.layer_types[self.index]

        def proj(x, name, *shape):
            w = self.param(name, init, shape, f32)
            with jax.named_scope("hvd.attn_proj"):
                return x @ w.astype(cfg.dtype)

        if kv is None:
            qkv = checkpoint_name(proj(u, "wqkv", d, (H + 2 * Hk) * D),
                                  QKV_NAME)
            q, k, v = jnp.split(qkv, (H * D, (H + Hk) * D), axis=-1)
            # A KV pair's [k1 | k2] and [v1 | v2] are 128-wide heads as
            # they lie.
            kv = tuple(x.reshape(B, T, Hk // 2, 2 * D) for x in (k, v))
        else:
            q = checkpoint_name(proj(u, "wq", d, H * D), QKV_NAME)
        with jax.named_scope("hvd.diff_attention"):
            # Head 2p + e: its 64 values in half e of a 128-wide head.
            q = _diff.lay_in_halves(q, D).reshape(B, T, H, 2 * D)
        o = causal_attention(
            q, *kv, window=cfg.sliding_window if kind == SLIDING else None,
            scale=D ** -0.5)
        with jax.named_scope("hvd.diff_attention"):
            lq1, lk1, lq2, lk2 = (self.param(n, _normal(0.1), (D,), f32)
                                  for n in ("lq1", "lk1", "lq2", "lk2"))
            lam0 = 0.8 - 0.6 * math.exp(-0.3 * cfg.layers[self.index])
            lam = jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + lam0
            scale = self.param("subln", nn.initializers.ones, (2 * D,), f32)
            a = _diff.diff_combine(o.reshape(B, T, H * 2 * D), lam,
                                   scale * (1.0 - lam0), cfg.layer_norm_eps)
        return proj(a, "wo", H * D, d), kv


class _GMU(nn.Module):
    cfg: SambaYConfig

    @nn.compact
    def __call__(self, u, m):
        cfg, d, Dn = self.cfg, self.cfg.hidden_size, self.cfg.d_inner
        init = _normal(cfg.initializer_range)
        w_in, w_out = (self.param(n, init, s, jnp.float32).astype(cfg.dtype)
                       for n, s in (("in_proj", (d, Dn)),
                                    ("out_proj", (Dn, d))))
        g = checkpoint_name(u @ w_in, GMU_IN_NAME)
        return (nn.silu(g.astype(jnp.float32)).astype(cfg.dtype) * m) @ w_out


class _Block(nn.Module):
    cfg: SambaYConfig
    index: int

    @nn.compact
    def __call__(self, x, m, kv):
        """(y, m, kv): ``m`` and ``kv`` are what the layers before handed
        on (None before any did), replaced where this layer hands on its
        own."""
        from ..monitor.registry import counter

        cfg, i = self.cfg, self.index
        kind = cfg.layer_types[i]
        u = _layer_norm(cfg, "ln1", x, eps=cfg.layer_norm_eps)
        if kind == MAMBA:
            with jax.named_scope("hvd.ssm"):
                mixed, made = _Mamba(cfg, name="mixer")(u)
            if cfg.hands_on(i):
                m = made
        elif kind == GMU:
            counter("shared.memory_readers").inc()
            with jax.named_scope("hvd.gmu"):
                mixed = _GMU(cfg, name="mixer")(u, m)
        else:
            if kind == CROSS:
                counter("shared.kv_readers").inc()
            mixed, made = _DiffAttention(cfg, i, name="mixer")(
                u, kv if kind == CROSS else None)
            if kind == FULL and cfg.hands_on(i):
                kv = made
        h = x + mixed
        z = _layer_norm(cfg, "ln2", h, eps=cfg.layer_norm_eps)
        with jax.named_scope("hvd.mlp"):
            y = h + _GatedMLP(cfg, cfg.intermediate_size, name="mlp")(z)
        return y, m, kv


class SambaY(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32, or the final
    normed hidden states [B, T, d] with ``cfg.return_hidden`` (for
    ``hvd.lm_head_loss(h, params["embed"], labels)``: the head is the
    embedding)."""
    cfg: SambaYConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        embed = self.param("embed", _normal(cfg.initializer_range),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with jax.named_scope("hvd.embed"):
            x = embed_lookup(embed, tokens, cfg.dtype)
        blocks = [_Block] * len(cfg.layers)
        if cfg.remat:
            blocks = rematerialised(
                _Block, remat_kept(cfg, *tokens.shape),
                remat_candidates(cfg, *tokens.shape), len(blocks),
                _flash.OUT_NAME, _scan.OUT_NAME)
        m = kv = None
        for i, block in enumerate(blocks):
            x, m, kv = block(cfg, i, name=f"h{i}")(x, m, kv)
        x = _layer_norm(cfg, "ln_f", x, eps=cfg.layer_norm_eps)
        if not cfg.return_hidden:
            x = jnp.einsum("btc,vc->btv", x, embed.astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
        return x
