"""A hybrid decoder whose every layer is ONE sublayer (``model_type``
``nemotron_h``: Nemotron-H / Nemotron 3): a Mamba-2 mixer, a grouped-KV
attention layer or a mixture of squared-ReLU experts that work in a latent
beside a full-width shared expert, the kind read layer by layer from
``hybrid_override_pattern`` (docs/ssd_scan.md, docs/moe.md).

A module beside ``sparse_moe_decoder.py`` and ``sambay.py`` and not new
kinds of their blocks: both are a mixer AND an MLP a layer, under two
norms; here a layer is ``x <- x + F_l(RMSNorm_l(x))`` with ``F_l`` one of
three things, and the state-space layer is Mamba-2's (one decay a head,
``ssm_state_size`` states, B and C shared by a group's heads, everything
from one in-projection, a gated norm over each group) where ``sambay.py``'s
is Mamba-1's. What they share is imported, not copied: the flash kernels'
call (``causal_attention``), RMSNorm (``_Scale``), the causal convolution
(``sambay.causal_conv``), the rule for what a rematerialised block keeps
(``kept_within`` / ``rematerialised`` with their value names), the router's
selection bias as state (``BIAS_COLLECTION``, ``update_router_biases``).

With ``u = RMSNorm_l(x)`` (eps ``layer_norm_epsilon``), no bias but the
convolution's, and ``h``, ``G``, ``Hq``, ``Hkv`` the Mamba heads, groups,
query and KV heads HELD here (the model is told its share; the published
counts divide by it):

* ``M``: ``[z | xBC | dt] = u W_in`` (widths ``P h``, ``P h + 2 N G``,
  ``h``; ``P`` ``mamba_head_dim``, ``N`` ``ssm_state_size``);
  ``xBC = silu(conv1d_causal(xBC) + b_conv)`` (depthwise, ``conv_kernel``
  taps); ``[xs | B | C] = xBC``; ``D_t = softplus(dt + dt_bias)``;
  ``y = hvd.ssd_scan(xs, D_t, A_log, B, C, Dskip)`` (``ops/ssd_scan.py``:
  ``S_t = exp(-exp(A_log) D_t) S_{t-1} + (D_t xs_t) (x) B_t``,
  ``y_t = S_t C_t + Dskip xs_t``, head ``i`` reading group
  ``i // (h / G)``); ``F = GroupRMSNorm(y * silu(z)) W_out``: the gate
  first, then the norm over each group's ``P h / G`` channels with a
  learned weight. Scope ``hvd.ssm`` with ``hvd.ssd_scan`` inside.
* ``*``: ``q, k, v = u W_q, u W_k, u W_v`` (``Hq`` query heads on ``Hkv``
  KV heads of ``head_dim``), no position of any kind, key ``s`` visible to
  query ``t`` iff ``s <= t``; ``F = softmax(q k^T / sqrt(head_dim)) v W_o``
  through the flash kernels.
* ``E``: ``s = sigmoid(u W_r)`` over ALL ``n_routed_experts`` in float32;
  the ``num_experts_per_tok`` largest of ``s + b`` are chosen (``b`` the
  selection bias: state in the collection ``router_bias``, moved by
  :func:`update_router_biases`, never by a gradient); gates
  ``routed_scaling_factor * s / sum(chosen s)``; ``l = u W_down`` (the
  latent, ``moe_latent_size`` wide); ``F = (sum_{e chosen, held} g_e
  relu(l W1_e)^2 W2_e) W_up + relu(u Ws1)^2 Ws2``. The router reads the
  full-width ``u`` (``hvd.moe_route``), the experts walk the latent
  (``hvd.moe_apply`` with two-matrix ``relu2`` experts, scope
  ``hvd.moe_ffn``), the two latent projections stand under
  ``hvd.moe_latent`` and the shared expert under ``hvd.shared_expert``.
  The layer is told which experts it holds (``first_local_expert``,
  ``num_local_experts``); what the absent ones would add is left out.

then a final RMSNorm and an untied head. bfloat16 activations and matmul
operands with float32 accumulation; float32 parameters, norms, the
convolution, softplus, the scan's decays and states, softmax statistics and
router scores.

Each layer is rematerialised in the backward pass. It always keeps its
input, the flash kernels' output with its log-sum-exp rows and the scan's
output with the chunk-entering states: the recomputed forward runs neither
a flash kernel nor a scan. Beside them, by ``kept_within``'s budget and in
this order: the experts' plan, the walk's output (the up-projection's
backward reads it: kept, the experts are not walked a third time), the
Mamba in-projection's output, the latent, q / k / v, and the shared
expert's hidden rows for as many of the LAST layers as fit.

Initial weights: normal(``initializer_range``) for every matrix and the
embedding, norm weights 1, the convolution's taps and bias
uniform(+-``conv_kernel``^-0.5), ``A_log = log(uniform(1, 16))``,
``Dskip = 1``, ``dt_bias`` the inverse softplus of a log-uniform draw in
[``time_step_min``, ``time_step_max``] floored at ``time_step_floor``, the
routers' biases 0.

Multi-token prediction (``num_nextn_predict_layers``) is not built: the
model is the next token's alone (ROADMAP R14).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..moe.layer import (ACTIVATIONS, PLAN_NAME, moe_apply, moe_route,
                         plan_bytes)
from ..ops import flash_attention as _flash
from ..ops import ssd_scan as _ssd
from ..ops.embed_lookup import embed_lookup
from .sambay import SSM_IN_NAME, _uniform, causal_conv
from .sparse_moe_decoder import (BIAS_COLLECTION, MLP_HIDDEN_NAME, QKV_NAME,
                                 _Scale, causal_attention, kept_within,
                                 rematerialised, update_router_biases)

__all__ = ["HybridMambaMoE", "HybridMambaMoEConfig", "MAMBA", "ATTENTION",
           "EXPERTS", "remat_candidates", "remat_kept",
           "update_router_biases"]

#: The letters of ``hybrid_override_pattern``.
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
KINDS = (MAMBA, ATTENTION, EXPERTS)

# ``checkpoint_name``s of a layer's values beside the other decoders' own.
LATENT_NAME = "hvd_block_moe_latent"
LATENT_OUT_NAME = "hvd_block_moe_latent_out"


@dataclass(frozen=True)
class HybridMambaMoEConfig:
    vocab_size: int = 131072
    pattern: str = "MEMEMEMEM*E"      # a kind a BUILT layer
    hidden_size: int = 4096
    layer_norm_epsilon: float = 1e-5
    # Mamba-2: the heads and groups held here.
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    # Attention: the query and KV heads held here.
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # Experts.
    n_routed_experts: int = 512       # the router's width
    num_local_experts: int = 512      # experts held here ...
    first_local_expert: int = 0       # ... starting at this one
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    load_balance_coeff: float = 0.0   # > 0: the selection bias as state
    initializer_range: float = 0.02

    dtype: jnp.dtype = jnp.bfloat16
    return_hidden: bool = False
    return_load: bool = False         # also {layer: token-choices [E]}

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution passes: ``[xs | B | C]``."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def in_width(self) -> int:
        """Columns of the in-projection: ``[z | xBC | dt]``."""
        return self.d_inner + self.conv_width + self.mamba_num_heads

    def has_router_bias(self) -> bool:
        return self.load_balance_coeff > 0

    @classmethod
    def from_dict(cls, cfg: dict, **overrides) -> "HybridMambaMoEConfig":
        """From a ``config.json`` as published (``model_type``
        ``nemotron_h``). ``layers``, where given, are the PUBLISHED indices
        of the layers to build, their kinds read from
        ``hybrid_override_pattern``; else the whole pattern is built. The
        head, group and expert counts are what is HELD here;
        ``published``, where given, holds the release's own counts under
        the same keys and every held count has to divide its published one
        by the same share as its partners (``validate``)."""
        if cfg.get("model_type") != "nemotron_h":
            raise ValueError("model_type is not nemotron_h")
        pattern = cfg["hybrid_override_pattern"]
        if len(pattern) != cfg["num_hidden_layers"]:
            raise ValueError(f"hybrid_override_pattern has {len(pattern)} "
                             f"letters for {cfg['num_hidden_layers']} layers")
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise NotImplementedError("group-limited routing")
        if cfg.get("mlp_hidden_act", "relu2") != "relu2" \
                or cfg.get("mamba_hidden_act", "silu") != "silu":
            raise NotImplementedError("activations other than relu2 experts "
                                      "and a silu mixer")
        flat = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        layers = cfg.get("layers", range(len(pattern)))
        flat["pattern"] = "".join(pattern[i] for i in layers)
        flat.setdefault("num_local_experts", cfg["n_routed_experts"])
        flat["routed_scaling_factor"] = float(cfg["routed_scaling_factor"])
        flat.update(overrides)
        out = cls(**flat)
        out.validate(cfg.get("published"))
        return out

    def validate(self, published: Optional[dict] = None) -> None:
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(f"pattern {self.pattern!r}: letters of {KINDS}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"{self.mamba_num_heads} Mamba heads over "
                             f"{self.n_groups} groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads over "
                             f"{self.num_key_value_heads} KV heads")
        if not (0 <= self.first_local_expert
                <= self.n_routed_experts - self.num_local_experts):
            raise ValueError("the experts held are not among the router's")
        for key, whole in (published or {}).items():
            held = getattr(self, key)
            if whole % held:
                raise ValueError(f"{key}: {held} held does not divide the "
                                 f"published {whole}")
        if published and ("mamba_num_heads" in published
                          and "n_groups" in published):
            if (published["mamba_num_heads"] * self.n_groups
                    != self.mamba_num_heads * published["n_groups"]):
                raise ValueError("the Mamba heads and their groups are "
                                 "shared out by different shares: a group's "
                                 "heads would not stay together")


def remat_candidates(cfg: HybridMambaMoEConfig, B: int, T: int) -> dict:
    """``{name: bytes a layer, one entry a layer}`` of what a layer's
    backward would otherwise make again, dearest per byte first
    (sparse_moe_decoder.py ``remat_candidates``' form). The walk's output
    is read by the up-projection's backward, so it stands right after the
    plan: kept, the recomputed forward walks no expert."""
    n = B * T
    row = n * jnp.dtype(cfg.dtype).itemsize          # a unit of width
    kinds = cfg.pattern
    plan = plan_bytes(n * cfg.num_experts_per_tok, cfg.num_local_experts)
    qkv = (cfg.num_attention_heads + 2 * cfg.num_key_value_heads) \
        * cfg.head_dim
    shared = cfg.n_shared_experts * cfg.moe_shared_expert_intermediate_size
    each = {
        PLAN_NAME: [plan * (k == EXPERTS) for k in kinds],
        LATENT_OUT_NAME: [row * cfg.moe_latent_size * (k == EXPERTS)
                          for k in kinds],
        SSM_IN_NAME: [row * cfg.in_width * (k == MAMBA) for k in kinds],
        LATENT_NAME: [row * cfg.moe_latent_size * (k == EXPERTS)
                      for k in kinds],
        QKV_NAME: [row * qkv * (k == ATTENTION) for k in kinds],
        MLP_HIDDEN_NAME: [row * shared * (k == EXPERTS) for k in kinds],
    }
    return {name: tuple(by) for name, by in each.items() if any(by)}


def remat_kept_anyway(cfg: HybridMambaMoEConfig, B: int, T: int) -> int:
    """Bytes the layers keep whatever the budget says: a layer's input; an
    attention layer's output with a float32 log-sum-exp a query head; a
    Mamba layer's scan output and the states its chunks start from."""
    n, item = B * T, jnp.dtype(cfg.dtype).itemsize
    total = 0
    for kind in cfg.pattern:
        total += n * cfg.hidden_size * item
        if kind == ATTENTION:
            total += n * cfg.num_attention_heads * (cfg.head_dim * item + 4)
        if kind == MAMBA:
            total += n * cfg.d_inner * item + B * -(-T // cfg.chunk_size) \
                * cfg.d_inner * cfg.ssm_state_size * 4
    return total


def remat_kept(cfg: HybridMambaMoEConfig, B: int, T: int,
               memory_bytes: Optional[int] = None) -> dict:
    return kept_within(remat_candidates(cfg, B, T),
                       remat_kept_anyway(cfg, B, T), memory_bytes)


def _normal(std):
    return nn.initializers.normal(std)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log`` of a uniform draw in [1, 16] a head (Mamba-2's)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(lo: float, hi: float, floor: float):
    """The inverse softplus of a log-uniform draw in [lo, hi], floored."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(lo), math.log(hi))), floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def group_rms_norm(x, weight, groups: int, eps: float):
    """RMSNorm over each of ``groups`` equal runs of the last dim, float32,
    times ``weight`` [last dim]."""
    x32 = x.astype(jnp.float32)
    g = x32.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(x.shape) * weight


class _Mamba2(nn.Module):
    cfg: HybridMambaMoEConfig

    @nn.compact
    def __call__(self, u):
        cfg, f32 = self.cfg, jnp.float32
        B, T, d = u.shape
        h, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                      cfg.ssm_state_size)
        Dn, Cw = cfg.d_inner, cfg.conv_width
        init = _normal(cfg.initializer_range)
        conv_init = _uniform(cfg.conv_kernel ** -0.5)

        def w(name, *shape):
            return self.param(name, init, shape, f32).astype(cfg.dtype)

        zxd = checkpoint_name(u @ w("in_proj", d, cfg.in_width), SSM_IN_NAME)
        z, xbc, dt = jnp.split(zxd, (Dn, Dn + Cw), axis=-1)
        xbc = nn.silu(causal_conv(
            xbc.astype(f32),
            self.param("conv_w", conv_init, (cfg.conv_kernel, Cw), f32),
            self.param("conv_b", conv_init, (Cw,), f32))).astype(cfg.dtype)
        xs, Bm, Cm = jnp.split(xbc, (Dn, Dn + G * N), axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + self.param(
            "dt_bias", _dt_bias_init(cfg.time_step_min, cfg.time_step_max,
                                     cfg.time_step_floor), (h,), f32))
        y = _ssd.ssd_scan(
            xs.reshape(B, T, h, P), dt,
            self.param("A_log", _a_log_init, (h,), f32),
            Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N),
            self.param("D", nn.initializers.ones, (h,), f32),
            chunk=cfg.chunk_size)
        gated = y.reshape(B, T, Dn).astype(f32) * nn.silu(z.astype(f32))
        normed = group_rms_norm(
            gated, self.param("norm", nn.initializers.ones, (Dn,), f32), G,
            cfg.layer_norm_epsilon)
        return normed.astype(cfg.dtype) @ w("out_proj", Dn, d)


class _Attention(nn.Module):
    cfg: HybridMambaMoEConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        B, T, d = u.shape
        H, Hk, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        init = _normal(cfg.initializer_range)

        def proj(x, name, *shape):
            w = self.param(name, init, shape, jnp.float32)
            with jax.named_scope("hvd.attn_proj"):
                return x @ w.astype(cfg.dtype)

        q, k, v = (checkpoint_name(proj(u, name, d, heads * D),
                                   QKV_NAME).reshape(B, T, heads, D)
                   for name, heads in (("wq", H), ("wk", Hk), ("wv", Hk)))
        o = causal_attention(q, k, v)          # no position: NoPE
        return proj(o.reshape(B, T, H * D), "wo", H * D, d)


class _LatentMoE(nn.Module):
    cfg: HybridMambaMoEConfig

    @nn.compact
    def __call__(self, u):
        """(F [B, T, d], token-choices per expert [E] of this rank's
        tokens)."""
        from ..monitor.registry import counter

        cfg, f32 = self.cfg, jnp.float32
        B, T, d = u.shape
        held, f, lat = (cfg.num_local_experts, cfg.moe_intermediate_size,
                        cfg.moe_latent_size)
        fs = cfg.n_shared_experts * cfg.moe_shared_expert_intermediate_size
        init = _normal(cfg.initializer_range)

        def w(name, *shape):
            return self.param(name, init, shape, f32)

        router = w("router", d, cfg.n_routed_experts)
        w_down, w_up = w("w_down", d, lat), w("w_up", lat, d)
        experts = {"w1": w("w1", held, lat, f), "w2": w("w2", held, f, lat)}
        bias = (self.variable(BIAS_COLLECTION, "bias", jnp.zeros,
                              (cfg.n_routed_experts,), f32).value
                if cfg.has_router_bias() else None)
        uf = u.reshape(B * T, d)
        plan = moe_route(
            uf, router, experts_per_token=cfg.num_experts_per_tok,
            first_expert=cfg.first_local_expert, held=held,
            scoring="sigmoid", bias=bias, route_norm=cfg.norm_topk_prob,
            route_scale=cfg.routed_scaling_factor)
        with jax.named_scope("hvd.moe_latent"):
            latent = checkpoint_name(uf @ w_down.astype(cfg.dtype),
                                     LATENT_NAME)
        walked = checkpoint_name(
            moe_apply(latent, plan, experts, activation="relu2"),
            LATENT_OUT_NAME)
        with jax.named_scope("hvd.moe_latent"):
            y = walked @ w_up.astype(cfg.dtype)
        if fs:
            counter("moe.shared_width").inc(fs)
            with jax.named_scope("hvd.shared_expert"):
                ws1 = self.param("shared_w1", init, (d, fs), f32)
                ws2 = self.param("shared_w2", init, (fs, d), f32)
                hidden = checkpoint_name(uf @ ws1.astype(cfg.dtype),
                                         MLP_HIDDEN_NAME)
                y = y + ACTIVATIONS["relu2"](hidden) @ ws2.astype(cfg.dtype)
        return y.reshape(B, T, d), plan.load


class _Block(nn.Module):
    cfg: HybridMambaMoEConfig
    index: int = 0

    @nn.compact
    def __call__(self, x):
        """(y, the layer's token-choices per expert or None)."""
        cfg = self.cfg
        kind = cfg.pattern[self.index]
        u = _Scale(cfg.layer_norm_epsilon, name="norm")(x)
        load = None
        if kind == MAMBA:
            with jax.named_scope("hvd.ssm"):
                out = _Mamba2(cfg, name="mixer")(u)
        elif kind == ATTENTION:
            out = _Attention(cfg, name="mixer")(u)
        else:
            out, load = _LatentMoE(cfg, name="moe")(u)
        return x + out, load


class HybridMambaMoE(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32, or the final
    normed hidden states [B, T, d] with ``cfg.return_hidden`` (for
    ``hvd.lm_head_loss(h, params["head"], labels)``: the head is untied).
    With ``cfg.return_load`` a pair: that, and ``{layer name: token-choices
    per expert [E]}`` of the expert layers (what
    :func:`update_router_biases` reads). With ``load_balance_coeff > 0``
    the model is applied with its ``router_bias`` collection beside
    ``params``."""
    cfg: HybridMambaMoEConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        init = _normal(cfg.initializer_range)
        embed = self.param("embed", init,
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        head = self.param("head", init,
                          (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with jax.named_scope("hvd.embed"):
            x = embed_lookup(embed, tokens, cfg.dtype)
        blocks = rematerialised(
            _Block, remat_kept(cfg, *tokens.shape),
            remat_candidates(cfg, *tokens.shape), len(cfg.pattern),
            _flash.OUT_NAME, _ssd.OUT_NAME)
        loads = {}
        for i, block in enumerate(blocks):
            x, load = block(cfg, i, name=f"h{i}")(x)
            if load is not None:
                loads[f"h{i}"] = load
        x = _Scale(cfg.layer_norm_epsilon, name="ln_f")(x)
        if not cfg.return_hidden:
            x = jnp.einsum("btc,vc->btv", x, head.astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
        return (x, loads) if cfg.return_load else x
