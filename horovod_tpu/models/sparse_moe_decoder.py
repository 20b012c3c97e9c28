"""One configuration-driven decoder block for the mixture-of-experts
families (docs/moe.md, docs/sparse_attention.md, docs/flash_window.md): a
layer's attention kind and MLP kind come from the configuration, layer by
layer, and the block is written once.

* attention kind (``SparseMoEConfig.layer_types``): ``"sparse"`` —
  grouped-KV attention behind a learned indexer, every query attending the
  ``topk`` keys its indexer scores highest (:func:`hvd.sparse_attention`);
  ``"sliding_attention"`` — causal attention over the last
  ``sliding_window`` keys; ``"full_attention"`` — causal attention over all
  of them; ``"block_diffusion"`` — attention under the block-diffusion
  objective's mask over ``[noised ; clean]`` rows (docs/block_diffusion.md).
  The last three run :func:`hvd.flash_attention` with grouped KV heads
  (``window=`` on a sliding layer, ``block_diffusion=`` on the last kind).
* MLP kind: a dense gated MLP on the first ``num_dense_layers`` layers,
  else routed experts through :func:`hvd.moe_ffn_dropless` (told which
  experts this chip holds), beside ``num_shared_experts`` shared ones that
  every token passes (scope ``hvd.shared_expert``, outside
  ``hvd.moe_ffn``). With ``router_input="block_input"`` the router reads
  the block's INPUT, before the attention norm: the block calls
  :func:`hvd.moe_route` on it ahead of attention (scope
  ``hvd.moe_route``) and :func:`hvd.moe_apply` on the normed stream after
  it.

Four published families are built from their own ``config.json`` keys
(:meth:`SparseMoEConfig.from_dict`, by ``model_type`` or, where the
release has none, by the keys only it has):

* Keye-VL-2.0's language model (``sa_config`` present): every layer
  ``sparse`` + routed, pre-norm residuals, rotary position on every layer,
  softmax top-k router.
* ``afmoe`` (Trinity): ``layer_types`` of sliding and full attention,
  sandwich norms (a norm before AND after each of attention and MLP), an
  output gate on attention (``o * sigmoid(u Wg)``), rotary position on the
  sliding layers only, an embedding scaled by ``sqrt(hidden_size)``, a
  sigmoid router whose selection bias is STATE: it lives in the flax
  collection ``router_bias`` (no gradient, no weight decay), the step
  carries it beside parameters and optimizer state, and
  :func:`update_router_biases` moves it once a step from the step's expert
  counts (``cfg.return_load`` hands them out).

* ``sdar_moe`` (SDAR): Qwen3-MoE's block (the first family's without the
  indexer) trained under the block-diffusion objective: every layer
  ``block_diffusion``, ``block_length`` from the configuration. The model
  is handed ``[B, 2 L]`` tokens, a noised copy of every sequence and then
  the clean one (:func:`hvd.block_diffusion_noise` makes them); both
  halves are rotated by positions ``0 .. L - 1``, every layer runs on all
  ``2 L`` rows, and the final norm and what follows it see the noised
  half alone: the hidden states or logits that come out are ``[B, L, ..]``
  (:func:`hvd.block_diffusion_loss` takes them).

* SmallThinker (``moe_num_primary_experts`` and ``sliding_window_layout``
  present; the release names itself in ``model_name``): the layers'
  kinds from ``sliding_window_layout`` (1 sliding, 0 full; the period is
  ``[full, sliding, sliding, sliding]``), rotary position where
  ``rope_layout`` says (the sliding layers; a full layer has none),
  pre-norm residuals, NO per-head norm on q and k, no gate, a softmax
  top-k router that reads the block's input, ReLU-gated experts
  (``W2(relu(W1 m) * W3 m)``), no shared expert and no dense layer.

RMSNorm, per-head RMSNorm on q and k (``qk_norm``; every family but the
last), no bias anywhere, an untied head.
bfloat16 activations and matmul operands with float32 accumulation;
float32 parameters, norms, rotary angles, softmax statistics and router
scores. Each block is rematerialised in the backward pass
(``jax.checkpoint`` with ``save_only_these_names``). It always keeps its
input, the attention kernel's output and log-sum-exp rows and (a sparse
layer) its selection at one bit a pair: the recomputed forward runs no
attention kernel. Beside them it keeps what is dear to make and cheap to
hold, in this order while all that the blocks keep, over all layers, stays
under ``KEEP_SHARE`` of the device's memory (:func:`remat_kept`: the
configuration, ``B``, ``T`` and the widths decide, nothing else): the
experts' plan (``PLAN_NAME``: no top-k and no sort again); under sandwich
norms, whose backward reads them, the MLP's or the mixture's output
(``MLP_OUT_NAME``: the experts are not walked a third time) and the
attention block's (``ATTN_OUT_NAME``); the q / k / v projections
(``QKV_NAME``) and the output gate's (``GATE_NAME``); the two hidden
projections of a dense MLP and a shared expert (``MLP_HIDDEN_NAME``). A
name that fits for every layer is kept by every layer, and the walk ends
at the FIRST that does not (:func:`kept_within`). Where that one is a
value a matmul reads (``FEEDS_A_MATMUL``: the hidden projections), it is
kept by as many layers as fit what is left of the share, the LAST layer
first: the backward runs from the last layer, so a late layer's values are
released while few gradients exist yet, and an early layer's would live
beside nearly all of them (the compiled step of the cell that keeps two
layers' hidden projections stands 0.20 GB lower for the last two than for
the first two: PERF.md, PR 47). q / k / v are kept by every layer or by
none: kept, they take the per-head norm and the rotation out of the
projection's fusion, and in the two cells where the share would hold some
layers' the step was longer for it (PERF.md, PR 47). The layers before the
run are rematerialised without the name, the others under every kept name
(:func:`rematerialised`: one ``nn.remat`` class where nothing is split).
What is still made again is elementwise (norms, rotation, gates, products,
the weights' bfloat16 casts), the router's matmul and, in the layers
before the run, the name that was split. Trace-time counters, once a
block: ``remat.kept_bytes{value=<name>}``,
``remat.kept_layers{value=<name>}`` (the layers that keep the name) and
``remat.kept_names``.

Initial weights: normal(``initializer_range``) for every matrix and the
embedding, ones for every RMSNorm scale, zeros for a router's bias.

Keye's vision tower is not built (on text tokens the three
``mrope_section`` position ids coincide), nor the indexer's own training
loss: under the language-model loss alone the indexer's weights receive a
zero gradient (ROADMAP R0).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..moe.layer import (ACTIVATIONS, PLAN_NAME, moe_apply,
                         moe_ffn_dropless, moe_route, plan_bytes,
                         router_bias_update)
from ..ops import flash_attention as _flash
from ..ops.embed_lookup import embed_lookup
from ..ops.sparse_attention import (OUT_NAME, SELECTION_NAME,
                                    sparse_attention)

SPARSE, SLIDING, FULL = "sparse", "sliding_attention", "full_attention"
BLOCK_DIFFUSION = "block_diffusion"
BIAS_COLLECTION = "router_bias"
#: What a layer's router reads: the normed stream its experts read, after
#: attention, or the block's input, before the attention norm.
ROUTER_INPUTS = ("mlp_input", "block_input")

# ``checkpoint_name``s of a block's values (``PLAN_NAME`` is the expert
# layer's own): what :func:`remat_kept` chooses among.
MLP_OUT_NAME = "hvd_block_mlp_out"
ATTN_OUT_NAME = "hvd_block_attn_out"
QKV_NAME = "hvd_block_qkv"
GATE_NAME = "hvd_block_gate"
MLP_HIDDEN_NAME = "hvd_block_mlp_hidden"
#: The candidates that some layers may keep and others make again
#: (:func:`kept_within`): values that a matmul reads, through no more than
#: an elementwise product, so that a kept one costs its bytes and no more
#: (0.6 ms of a forward for 11.6 of a backward in ``phi-4-mini-flash``:
#: PERF.md, PR 47). Not q / k / v: a flash kernel reads them through the
#: per-head norm and the rotation, which ride in the projection's fusion
#: while it is made again and are float32 passes of their own over a kept
#: one (+18.5 ms of ``hvd.norm`` for 11 ms of projections in
#: ``sdar-30b-a3b``: PERF.md, PR 47). The other names are not measured.
FEEDS_A_MATMUL = frozenset({MLP_HIDDEN_NAME})

#: The share of the device's memory that what the blocks keep, over all
#: layers, may take: what they keep whatever the rule says (input, attention
#: output, selection), the candidates in their order that fit for every
#: layer and, where the next one ``FEEDS_A_MATMUL``, the last layers of it
#: that fit what is left. At an eighth ``trinity-mini`` (0.51 GB kept
#: anyway) keeps all six candidates, 1.43 GB more, and its compiled step
#: stands at 12.25 GB of 16 (11.56 with none); the sparse cell (1.42 GB
#: anyway, one 16k sequence) keeps the plan, 14.70 GB, and not its 1.0 GB of
#: q / k / v, with which it would stand at 15.68 (15.37 with the last four
#: layers' 0.67, and a longer step: PERF.md, PRs 38 and 47, have the
#: compiled step at each set).
KEEP_SHARE = 0.125
#: A device that reports no memory (the CPU) is taken for a TPU v5e.
ASSUMED_MEMORY_BYTES = 16 * 2 ** 30


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

    # A layer's kinds. ``layer_types`` None: every layer ``sparse``.
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: Optional[int] = None
    block_length: Optional[int] = None   # of ``block_diffusion`` layers
    num_dense_layers: int = 0         # leading layers with a dense MLP ...
    intermediate_size: int = 0        # ... of this width
    num_shared_experts: int = 0
    # The router (moe/layer.py ``moe_router``).
    scoring: str = "softmax"
    route_norm: bool = True
    route_scale: float = 1.0
    load_balance_coeff: float = 0.0   # > 0: a selection bias as state
    router_input: str = "mlp_input"   # or "block_input": before attention
    expert_activation: str = "silu"   # or "relu" (moe/layer.py ACTIVATIONS)
    # The block around them.
    qk_norm: bool = True              # RMSNorm over every q and k head
    sandwich_norms: bool = False      # a norm after attention and MLP too
    attention_gate: bool = False      # o * sigmoid(u Wg)
    rope_layers: str = "all"          # or "sliding": NoPE on full layers
    embed_scale: float = 1.0

    dtype: jnp.dtype = jnp.bfloat16
    return_hidden: bool = False
    return_load: bool = False         # also {layer: token-choices [E]}

    def __post_init__(self):
        if self.router_input not in ROUTER_INPUTS:
            raise ValueError(f"router_input is one of {ROUTER_INPUTS}, got "
                             f"{self.router_input!r}")
        if self.expert_activation not in ACTIVATIONS:
            raise ValueError(f"expert_activation is one of "
                             f"{tuple(ACTIVATIONS)}, got "
                             f"{self.expert_activation!r}")

    def attention_kind(self, i: int) -> str:
        return SPARSE if self.layer_types is None else self.layer_types[i]

    def has_router_bias(self) -> bool:
        return self.scoring == "sigmoid" and self.load_balance_coeff > 0

    @classmethod
    def from_dict(cls, cfg: dict, **overrides) -> "SparseMoEConfig":
        """From a ``config.json`` as published; ``layers`` is the depth to
        build where given, else ``num_hidden_layers``. The family is told
        by its keys: a nested ``sa_config`` (the learned indexer),
        ``model_type`` ``afmoe`` or ``sdar_moe`` (``block_length`` is the
        release's generation setting, not a key of its ``config.json``:
        the configuration that is built states it), or SmallThinker's own
        ``moe_num_primary_experts`` and ``sliding_window_layout`` (its
        ``config.json`` has no ``model_type``; ``num_local_experts``
        defaults to all of them)."""
        flat = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        flat.setdefault("layers", cfg.get("num_hidden_layers"))
        flat["rope_theta"] = float(cfg["rope_theta"])
        if "sa_config" in cfg:
            sa = cfg["sa_config"]
            if sa.get("indexer_num_kv_heads", 1) != 1:
                raise ValueError("the indexer is built for one key head")
            for key in ("layer_types", "sliding_window", "intermediate_size"):
                flat.pop(key, None)   # published, and unused by this family
            flat.update(indexer_num_heads=sa["indexer_num_heads"],
                        indexer_head_dim=sa["indexer_head_dim"],
                        topk=sa["topk"])
        elif cfg.get("model_type") == "afmoe":
            kinds = tuple(cfg["layer_types"])[:flat["layers"]]
            if len(kinds) != flat["layers"] or set(kinds) - {SLIDING, FULL}:
                raise ValueError(f"layer_types {kinds} for {flat['layers']} "
                                 f"layers")
            if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
                raise NotImplementedError("group-limited routing")
            flat.update(
                layer_types=kinds, scoring=cfg.get("score_func", "sigmoid"),
                sandwich_norms=True, attention_gate=True,
                rope_layers="sliding",
                embed_scale=(cfg["hidden_size"] ** 0.5
                             if cfg.get("mup_enabled") else 1.0))
        elif cfg.get("model_type") == "sdar_moe":
            for key in ("sliding_window", "intermediate_size"):
                flat.pop(key, None)   # published, and unused by this family
            flat.update(layer_types=(BLOCK_DIFFUSION,) * flat["layers"],
                        block_length=int(cfg["block_length"]))
        elif {"moe_num_primary_experts", "sliding_window_layout"} <= set(cfg):
            n = flat["layers"]
            sliding, roped = (tuple(cfg[k])[:n] for k in (
                "sliding_window_layout", "rope_layout"))
            if len(sliding) != n or set(sliding) - {0, 1}:
                raise ValueError(f"sliding_window_layout {sliding} for {n} "
                                 f"layers")
            if roped not in (sliding, (1,) * n):
                raise NotImplementedError(
                    f"rope_layout {roped}: position on the sliding layers "
                    f"{sliding} or on every layer")
            if not (cfg.get("moe_primary_router_apply_softmax", True)
                    and cfg.get("norm_topk_prob", True)):
                raise NotImplementedError(
                    "a router without the softmax over its chosen logits")
            flat.update(
                layer_types=tuple(SLIDING if w else FULL for w in sliding),
                rope_layers="all" if roped != sliding else "sliding",
                sliding_window=cfg["sliding_window_size"],
                num_experts=cfg["moe_num_primary_experts"],
                num_experts_per_tok=cfg["moe_num_active_primary_experts"],
                moe_intermediate_size=cfg["moe_ffn_hidden_size"],
                qk_norm=False, router_input="block_input",
                expert_activation="relu")
            flat.setdefault("num_local_experts", flat["num_experts"])
        else:
            raise ValueError(
                "no sa_config, no model_type afmoe or sdar_moe, no "
                "moe_num_primary_experts with a sliding_window_layout: a "
                "family this decoder does not know")
        flat.update(overrides)
        return cls(**flat)


@functools.lru_cache(maxsize=None)
def device_memory_bytes() -> int:
    """The first local device's memory, read once; a device that reports
    none is taken for ``ASSUMED_MEMORY_BYTES``."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit") or ASSUMED_MEMORY_BYTES)


def remat_candidates(cfg: SparseMoEConfig, B: int, T: int) -> dict:
    """``{name: bytes a layer, one entry a layer}`` of what a block's
    backward would otherwise make again, dearest per byte first, for
    ``B x T`` tokens a chip: a name a layer does not hold reads 0 there,
    and a name no layer's backward would read is left out. The plan is
    the same bytes and stands first wherever the router reads: from the
    block's input it no longer hangs on the attention's output, and what
    keeping it spares, a top-k and a sort, is the same."""
    n = B * T
    row = n * jnp.dtype(cfg.dtype).itemsize          # a unit of width
    H, Hk, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    routed = [i >= cfg.num_dense_layers for i in range(cfg.layers)]
    plan = plan_bytes(n * cfg.num_experts_per_tok, cfg.num_local_experts)
    shared = cfg.num_shared_experts * cfg.moe_intermediate_size
    # Only a norm after attention and MLP reads what they put out.
    normed = [row * cfg.hidden_size * cfg.sandwich_norms] * cfg.layers
    each = {
        PLAN_NAME: [plan * r for r in routed],
        MLP_OUT_NAME: normed,
        ATTN_OUT_NAME: normed,
        QKV_NAME: [row * (H + 2 * Hk) * D] * cfg.layers,
        GATE_NAME: [row * H * D * cfg.attention_gate] * cfg.layers,
        MLP_HIDDEN_NAME: [2 * row * (shared if r else cfg.intermediate_size)
                          for r in routed],
    }
    return {name: tuple(by) for name, by in each.items() if any(by)}


def remat_kept_anyway(cfg: SparseMoEConfig, B: int, T: int) -> int:
    """Bytes the blocks keep whatever :func:`remat_kept` says, over all
    layers: a block's input, the attention kernel's output with a float32
    log-sum-exp a query head, a sparse layer's selection at a bit a pair."""
    n, item = B * T, jnp.dtype(cfg.dtype).itemsize
    H, D = cfg.num_attention_heads, cfg.head_dim
    sparse = sum(cfg.attention_kind(i) == SPARSE for i in range(cfg.layers))
    return (cfg.layers * n * ((cfg.hidden_size + H * D) * item + 4 * H)
            + sparse * B * T * T // 8)


def kept_within(candidates: dict, anyway: int,
                memory_bytes: Optional[int] = None) -> dict:
    """``{name: bytes a layer}`` of what the blocks keep of ``candidates``
    (the same form) within ``KEEP_SHARE`` of the device's memory
    (``memory_bytes``, else :func:`device_memory_bytes`), ``anyway``
    counted in: the candidates in their order, each for every layer, while
    they fit whole, and nothing after the first that does not. That one,
    where it ``FEEDS_A_MATMUL``, is kept for the longest run of layers from
    the LAST towards the first whose bytes fit what is left (0 for a layer
    before the run; the name is left out where the run holds none of it).
    Bytes decide how much, the name whether a part is worth keeping."""
    left = KEEP_SHARE * (memory_bytes or device_memory_bytes()) - anyway
    kept = {}
    for name, by_layer in candidates.items():
        if sum(by_layer) > left:
            first = len(by_layer)
            while (name in FEEDS_A_MATMUL and first
                   and by_layer[first - 1] <= left):
                first -= 1
                left -= by_layer[first]
            if any(by_layer[first:]):
                kept[name] = (0,) * first + tuple(by_layer[first:])
            break
        left -= sum(by_layer)
        kept[name] = by_layer
    return kept


def remat_kept(cfg: SparseMoEConfig, B: int, T: int,
               memory_bytes: Optional[int] = None) -> dict:
    """The candidates a block keeps: :func:`remat_candidates` in their
    order within :func:`kept_within`'s budget, beside
    :func:`remat_kept_anyway`."""
    return kept_within(remat_candidates(cfg, B, T),
                       remat_kept_anyway(cfg, B, T), memory_bytes)


def rematerialised(block, kept: dict, candidates: dict, layers: int,
                   *anyway) -> list:
    """Layer by layer, the module class ``block`` rematerialised under
    ``save_only_these_names`` of ``anyway`` and the names of ``kept``
    (:func:`kept_within`'s dict of ``candidates``): ONE ``nn.remat`` class
    under every kept name, and a second, without the name that was split,
    for the layers whose entry for it is less than the candidate's. Counts
    what it keeps, once a block: ``remat.kept_names``,
    ``remat.kept_bytes{value=}`` and ``remat.kept_layers{value=}``."""
    from ..monitor.registry import counter

    def under(names):
        return nn.remat(
            block, policy=jax.checkpoint_policies.save_only_these_names(
                *anyway, *names))

    split = [name for name in kept if kept[name] != candidates[name]]
    whole = under(kept)
    short = under(n for n in kept if n not in split) if split else whole
    classes = []
    for i in range(layers):
        counter("remat.kept_names").inc(len(kept))
        for name, by_layer in kept.items():
            counter("remat.kept_bytes", value=name).inc(by_layer[i])
            counter("remat.kept_layers", value=name).inc(by_layer[i] > 0)
        before = any(kept[n][i] < candidates[n][i] for n in split)
        classes.append(short if before else whole)
    return classes


def rms_norm_in_scope(x, scale, eps):
    """RMSNorm over the last dim in float32, under the caller's device
    scope."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rms_norm(x, scale, eps):
    with jax.named_scope("hvd.norm"):
        return rms_norm_in_scope(x, scale, eps)


def rope(x, theta: float, positions=None):
    """Rotary embedding over the last dim of x [B, T, heads, D], positions
    0..T-1 or the ``positions [T]`` given, halves rotated; float32
    angles."""
    T, D = x.shape[1], x.shape[-1]
    with jax.named_scope("hvd.rotary"):
        inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        at = (jnp.arange(T, dtype=jnp.float32) if positions is None
              else positions.astype(jnp.float32))
        ang = at[:, None] * inv[None]
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
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
            with jax.named_scope("hvd.attn_proj"):
                return u @ lax.stop_gradient(w).astype(cfg.dtype)

        qi = rope(proj("wq", Hi * Di).reshape(B, T, Hi, Di), cfg.rope_theta)
        ki = rope(proj("wk", Di)[:, :, None, :], cfg.rope_theta)[:, :, 0]
        return qi, ki, proj("ww", Hi).astype(jnp.float32)


def causal_attention(q, k, v, window: Optional[int] = None,
                     scale: Optional[float] = None):
    """The models' one call of the flash kernels: causal, grouped KV heads,
    ``window`` keys a query where given. ``scale`` is handed on only where
    given, so that a caller that gives none makes the call it always made,
    ``causal`` and ``window`` alone: ``tests/benchmark/test_bench_afmoe.py``
    puts a stand-in of that signature in the kernels' place."""
    extra = {} if scale is None else {"scale": scale}
    return _flash.flash_attention(q, k, v, causal=True, window=window,
                                  **extra)


def block_diffusion_attention(q, k, v, block_length: int):
    """The flash kernels under the block-diffusion mask: q, k, v hold the
    noised rows of every sequence and then its clean rows."""
    return _flash.flash_attention(q, k, v, block_diffusion=block_length)


def _output_gate(o, g):
    """o * sigmoid(g), the sigmoid in float32."""
    return o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(o.dtype)


class _Attention(nn.Module):
    cfg: SparseMoEConfig
    kind: str = SPARSE

    @nn.compact
    def __call__(self, u, index=None, positions=None):
        cfg, kind = self.cfg, self.kind
        B, T, d = u.shape
        H, Hk, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        init = nn.initializers.normal(cfg.initializer_range)

        def proj(x, name, *shape):
            w = self.param(name, init, shape, jnp.float32)
            with jax.named_scope("hvd.attn_proj"):
                return x @ w.astype(cfg.dtype)

        def head_norm(name, x):
            if not cfg.qk_norm:
                return x
            scale = self.param(name, nn.initializers.ones, (D,), jnp.float32)
            return rms_norm(x, scale, cfg.rms_norm_eps)

        def qkv(name, heads):
            return checkpoint_name(proj(u, name, d, heads * D),
                                   QKV_NAME).reshape(B, T, heads, D)

        q = head_norm("q_norm", qkv("wq", H))
        k = head_norm("k_norm", qkv("wk", Hk))
        v = qkv("wv", Hk)
        if cfg.rope_layers == "all" or kind == SLIDING:
            q, k = (rope(x, cfg.rope_theta, positions) for x in (q, k))
        if kind == SPARSE:
            o = sparse_attention(q, k, v, *index, topk=cfg.topk)
        elif kind == BLOCK_DIFFUSION:
            o = block_diffusion_attention(q, k, v, cfg.block_length)
        else:
            o = causal_attention(
                q, k, v, cfg.sliding_window if kind == SLIDING else None)
        o = o.reshape(B, T, H * D)
        if cfg.attention_gate:
            o = _output_gate(o, checkpoint_name(proj(u, "wg", d, H * D),
                                                GATE_NAME))
        return checkpoint_name(proj(o, "wo", H * D, d), ATTN_OUT_NAME)


class _GatedMLP(nn.Module):
    """W2(silu(W1 z) * W3 z): the dense layers' MLP and a shared expert."""
    cfg: SparseMoEConfig
    width: int

    @nn.compact
    def __call__(self, z):
        cfg, d, f = self.cfg, z.shape[-1], self.width
        init = nn.initializers.normal(cfg.initializer_range)
        w1, w3 = (self.param(n, init, (d, f), jnp.float32).astype(cfg.dtype)
                  for n in ("w1", "w3"))
        w2 = self.param("w2", init, (f, d), jnp.float32).astype(cfg.dtype)
        a, b = (checkpoint_name(z @ w, MLP_HIDDEN_NAME) for w in (w1, w3))
        return (nn.silu(a) * b) @ w2


class _MoE(nn.Module):
    """The routed experts of a layer: ``route`` makes the plan from the
    tensor the router reads, the call walks the experts' input by it.
    Where both are the one tensor (``cfg.router_input == "mlp_input"``)
    the call alone does both, as :func:`hvd.moe_ffn_dropless`."""
    cfg: SparseMoEConfig

    def setup(self):
        cfg = self.cfg
        d, held, f = (cfg.hidden_size, cfg.num_local_experts,
                      cfg.moe_intermediate_size)
        init = nn.initializers.normal(cfg.initializer_range)
        self.router = self.param("router", init, (d, cfg.num_experts),
                                 jnp.float32)
        self.w1 = self.param("w1", init, (held, d, f), jnp.float32)
        self.w3 = self.param("w3", init, (held, d, f), jnp.float32)
        self.w2 = self.param("w2", init, (held, f, d), jnp.float32)
        if cfg.has_router_bias():
            self.bias = self.variable(BIAS_COLLECTION, "bias", jnp.zeros,
                                      (cfg.num_experts,), jnp.float32)
        if cfg.num_shared_experts:
            self.shared = _GatedMLP(cfg, cfg.num_shared_experts * f)

    def _routing(self) -> dict:
        cfg = self.cfg
        kw = dict(experts_per_token=cfg.num_experts_per_tok,
                  first_expert=cfg.first_local_expert)
        if cfg.scoring != "softmax":
            kw.update(scoring=cfg.scoring, route_norm=cfg.route_norm,
                      route_scale=cfg.route_scale)
        if cfg.has_router_bias():
            kw["bias"] = self.bias.value
        return kw

    def route(self, x):
        """The plan of the block's tokens from ``x [B, T, d]``, the tensor
        the router reads."""
        return moe_route(x.reshape(-1, x.shape[-1]), self.router,
                         held=self.cfg.num_local_experts, **self._routing())

    def __call__(self, z, plan=None):
        """(y, token-choices per expert [E] of this rank's tokens) of the
        experts' input ``z [B, T, d]``, routed by ``plan`` where given and
        from ``z`` itself otherwise."""
        from ..monitor.registry import counter

        cfg = self.cfg
        B, T, d = z.shape
        params = {"router": self.router, "w1": self.w1, "w3": self.w3,
                  "w2": self.w2}
        counter("moe.router_input", at=cfg.router_input).inc()
        if plan is None:
            y, aux = moe_ffn_dropless(
                z.reshape(B * T, d), params, **self._routing(),
                activation=cfg.expert_activation)
            load = aux.load
        else:
            y = moe_apply(z.reshape(B * T, d), plan, params,
                          activation=cfg.expert_activation)
            load = plan.load
        self.sow("intermediates", "moe_expert_load", load)
        y = y.reshape(B, T, d)
        if cfg.num_shared_experts:
            counter("moe.shared_width").inc(
                cfg.num_shared_experts * cfg.moe_intermediate_size)
            with jax.named_scope("hvd.shared_expert"):
                y = y + self.shared(z)
        return y, load


class _Block(nn.Module):
    cfg: SparseMoEConfig
    index: int = 0

    @nn.compact
    def __call__(self, x, positions=None):
        """(y, the layer's token-choices per expert or None)."""
        cfg, i = self.cfg, self.index
        kind = cfg.attention_kind(i)
        routed = i >= cfg.num_dense_layers

        def norm(name, t):
            return _Scale(cfg.rms_norm_eps, name=name)(t)

        def after(name, t):
            return norm(name, t) if cfg.sandwich_norms else t

        moe = _MoE(cfg, name="moe") if routed else None
        # A router that reads the block's input is run here, ahead of
        # attention: its plan hangs on nothing attention makes.
        plan = (moe.route(x) if routed and cfg.router_input == "block_input"
                else None)
        if plan is not None:
            # Said to the compiler, not left to its scheduler, which is
            # free to run two independent pieces in either order (PR 46
            # saw one layer's routing move behind its attention kernel when
            # the lookup ahead of the blocks changed): attention reads the
            # stream only once the plan is made.
            plan, x = jax.lax.optimization_barrier((plan, x))
        u = norm("ln1", x)
        index = (_Indexer(cfg, name="indexer")(u),) if kind == SPARSE else ()
        h = x + after("ln1_post", _Attention(cfg, kind, name="attn")(
            u, *index, positions=positions))
        z = norm("ln2", h)
        if routed:
            m, load = moe(z, plan)
        else:
            with jax.named_scope("hvd.mlp"):
                m = _GatedMLP(cfg, cfg.intermediate_size, name="mlp")(z)
            load = None
        return h + after("ln2_post", checkpoint_name(m, MLP_OUT_NAME)), load


class SparseMoEDecoder(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32, or the final
    normed hidden states [B, T, d] with ``cfg.return_hidden`` (for
    ``hvd.lm_head_loss(h, params["head"], labels)``: the head is untied).
    With ``cfg.return_load`` a pair: that, and ``{layer name: token-choices
    per expert [E]}`` of the routed layers (what
    :func:`update_router_biases` reads). A family with a router bias is
    applied with its ``router_bias`` collection beside ``params``. With
    ``cfg.block_length`` the tokens are ``[B, 2 L]``, noised rows then
    clean ones, and what comes out is the noised half's, ``[B, L, ..]``."""
    cfg: SparseMoEConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        init = nn.initializers.normal(cfg.initializer_range)
        embed = self.param("embed", init,
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        head = self.param("head", init,
                          (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with jax.named_scope("hvd.embed"):
            x = embed_lookup(embed, tokens, cfg.dtype)
            if cfg.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
        positions = None
        if cfg.block_length is not None:
            if tokens.shape[1] % (2 * cfg.block_length):
                raise ValueError(
                    f"{tokens.shape[1]} rows are not a noised and a clean "
                    f"copy of whole blocks of {cfg.block_length}")
            half = tokens.shape[1] // 2
            positions = jnp.arange(2 * half) % half
        blocks = rematerialised(
            _Block, remat_kept(cfg, *tokens.shape),
            remat_candidates(cfg, *tokens.shape), cfg.layers, OUT_NAME,
            SELECTION_NAME, _flash.OUT_NAME)
        loads = {}
        for i, block in enumerate(blocks):
            x, load = block(cfg, i, name=f"h{i}")(x, positions)
            if load is not None:
                loads[f"h{i}"] = load
        if cfg.block_length is not None:
            x = x[:, :half]    # the head reads the noised half alone
        x = _Scale(cfg.rms_norm_eps, name="ln_f")(x)
        if not cfg.return_hidden:
            x = jnp.einsum("btc,vc->btv", x, head.astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
        return (x, loads) if cfg.return_load else x


def update_router_biases(biases, loads, *, coeff: float, reduce=None):
    """The routers' selection biases after one step: ``biases`` is the
    model's ``router_bias`` collection (``{layer: {"moe": {"bias": [E]}}}``),
    ``loads`` what the model returned with ``cfg.return_load``. ``reduce``
    sums a count over the data axes (``lambda n: hvd.allreduce(n,
    op=hvd.Sum)`` inside the step's ``shard_map``; nothing to exchange on
    one chip) so that every rank holds the same biases. The rule is
    :func:`hvd.router_bias_update`'s."""
    with jax.named_scope("hvd.router_bias_update"):
        return {layer: {"moe": {"bias": router_bias_update(
            tree["moe"]["bias"],
            loads[layer] if reduce is None else reduce(loads[layer]),
            coeff=coeff)}} for layer, tree in biases.items()}
