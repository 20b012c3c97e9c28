"""GPT-style decoder-only transformer with first-class sequence parallelism.

No counterpart exists in the reference (it is a CNN-era data-parallel
framework, SURVEY §5.7); this is the long-context flagship of the TPU
build. TPU-first choices:

* bfloat16 activations, fp32 params/softmax statistics (MXU-native),
* pre-norm blocks, GELU MLP, learned positional embeddings,
* attention is pluggable: ``dense`` (single chip), ``flash`` (Pallas
  flash kernel, :mod:`horovod_tpu.ops.flash_attention` — same numerics,
  no [T, T] HBM round-trip), ``ring`` (ppermute ring over the mesh axis —
  O(T/n) sequence memory/chip), or ``ulysses`` (all-to-all head exchange,
  local attention runs the flash kernel) from
  :mod:`horovod_tpu.parallel.sequence`,
* optional ``remat`` per block (jax.checkpoint) to trade FLOPs for HBM,
* everything is static-shaped, scan-free python loops over layers so XLA
  fuses each block independently.

Under sequence parallelism, ``__call__`` must run inside ``jax.shard_map``
with ``tokens`` sharded on the sequence axis; positional embeddings are
offset by the chip's shard index automatically.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..common.basics import LOCAL_AXIS
from ..parallel import sequence as seqpar


def _layer_norm(cfg, name: str, x, eps: float = 1e-6):
    """``nn.LayerNorm`` under the device scope ``hvd.norm`` (``eps``: flax's
    default unless a family publishes its own)."""
    with jax.named_scope("hvd.norm"):
        return nn.LayerNorm(epsilon=eps, dtype=cfg.dtype, name=name)(x)


def _dense_mlp(cfg, x):
    """The block's dense MLP under the device scope ``hvd.mlp``."""
    with jax.named_scope("hvd.mlp"):
        return _MLP(cfg, name="mlp")(x)


def _tp_size(cfg) -> int:
    """Bound size of the tensor-parallel axis (1 outside shard_map)."""
    return seqpar._axis_size(cfg.tp_axis) if cfg.tp_axis else 1


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "dense"    # dense | flash | ring | flash_ring | ulysses
    seq_axis: str = LOCAL_AXIS        # mesh axis carrying the sequence
    remat: bool = False
    embed_init_std: float = 0.02
    # Megatron-style tensor parallelism: when set and bound inside
    # shard_map, attention heads and d_ff shard over this mesh axis —
    # qkv/fc1 are column-parallel (local output slices), proj/fc2 are
    # row-parallel (partial sums combined by one psum per block half).
    # Parameters must be the LOCAL shards; see
    # horovod_tpu.parallel.tensor.tp_shard_params for slicing a dense
    # checkpoint. Composes with DP on the other axis (and with the
    # non-ring attention modes).
    tp_axis: Optional[str] = None
    # Mixture-of-Experts: > 0 replaces every block's dense MLP with a
    # Switch-MoE FFN of this many (GLOBAL) experts; with ep_axis bound
    # inside shard_map, experts shard over that mesh axis and tokens are
    # exchanged by all-to-all (parallel/expert.py). The router's
    # load-balancing aux losses are sown under
    # intermediates/.../moe_aux_loss.
    moe_experts: int = 0
    ep_axis: Optional[str] = None
    moe_capacity_factor: float = 1.25
    # Ragged (uneven-alltoall) expert dispatch: pools expert capacity
    # across senders instead of a per-(sender, expert) quota (reference
    # uneven-splits path: operations.cc:1031-1092).
    # moe_pair_capacity_factor bounds each (sender -> rank) block at
    # factor * N / n rows.
    moe_ragged: bool = False
    moe_pair_capacity_factor: float = 2.0
    # Fused residual-add + LayerNorm Pallas kernel for each block's
    # second LN (ops/layer_norm.py): saves one HBM round trip of the
    # [B, T, C] stream per block when XLA does not fuse the add into the
    # LN reductions. Param tree is identical either way (ln2/scale,
    # ln2/bias), so checkpoints are interchangeable.
    fused_ln: bool = False
    # Return the final-LayerNorm hidden states [B, T, d_model] instead of
    # logits — for a fused LM-head loss (ops/softmax_xent.py) that never
    # materializes the [N, vocab] logits. Parameters are identical either
    # way (wte is created for the embedding lookup regardless).
    return_hidden: bool = False
    # Decode-time KV paging (horovod_tpu/serve/kv_cache.py): when set, the
    # cache's pages stripe round-robin over this mesh axis — contexts
    # longer than one host's page pool — and decode attention merges
    # per-rank flash partials with the ring-attention combine. Must be
    # disjoint from tp_axis (same constraint as seq_axis: the stripe would
    # otherwise rotate between ranks holding different heads). Only
    # affects the cache path (__call__ with cache=); training modes are
    # governed by ``attention``/``seq_axis`` as before.
    kv_ring_axis: Optional[str] = None


class _Attention(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, decode=None):
        cfg = self.cfg
        B, T, C = x.shape
        tp = _tp_size(cfg)
        if cfg.num_heads % tp:
            raise ValueError(
                f"num_heads {cfg.num_heads} not divisible by "
                f"tp axis size {tp}")
        if tp > 1 and cfg.attention in ("ring", "flash_ring", "ulysses"):
            tp_axes = ({cfg.tp_axis} if isinstance(cfg.tp_axis, str)
                       else set(cfg.tp_axis))
            seq_axes = ({cfg.seq_axis} if isinstance(cfg.seq_axis, str)
                        else set(cfg.seq_axis))
            if tp_axes & seq_axes:
                # Same mesh axis cannot carry both head shards and
                # sequence shards — the ring would rotate k/v between
                # ranks holding DIFFERENT heads and silently produce
                # garbage. Distinct axes (e.g. tp=local, seq=cross)
                # compose fine.
                raise ValueError(
                    f"tp_axis {cfg.tp_axis!r} overlaps seq_axis "
                    f"{cfg.seq_axis!r} under attention="
                    f"{cfg.attention!r}; use disjoint mesh axes")
        H = cfg.num_heads // tp   # local heads (column-parallel qkv)
        D = C // cfg.num_heads
        with jax.named_scope("hvd.attn_proj"):
            qkv = nn.Dense(3 * H * D, dtype=cfg.dtype, name="qkv",
                           kernel_init=nn.initializers.normal(0.02))(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        # The 4-D views are free: ``flash_attention`` folds them back and
        # its kernels index [B, T, H * D] as it lies (in the compiled step
        # the split's three slices are the forward kernel's operands).
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, H, D)
        v = v.reshape(B, T, H, D)

        def project(out):
            with jax.named_scope("hvd.attn_proj"):
                return nn.Dense(C, dtype=cfg.dtype, name="proj",
                                kernel_init=nn.initializers.normal(
                                    0.02 / (2 * cfg.num_layers) ** 0.5))(out)

        if decode is not None:
            # Paged single-token decode (serve/kv_cache.py): append this
            # step's k/v to the layer's page pool, attend over the slot's
            # cached pages. The TRAINING attention mode (dense/flash/ring)
            # is irrelevant here — the cache IS the sequence; tp (local
            # heads + row-parallel proj psum) composes unchanged.
            from ..serve import kv_cache as kvlib

            cache, meta, layer = decode
            if meta.write_page.ndim == 2:
                # Windowed verify/prefill chunk: all T = W positions'
                # k/v land in one scatter, per-query masks keep each
                # position blind to its future.
                cache = kvlib.append_layer_kv(cache, layer, k, v, meta)
            else:
                cache = kvlib.append_layer_kv(cache, layer, k[:, 0],
                                              v[:, 0], meta)
            out = kvlib.paged_attention(
                q, cache.k[layer], cache.v[layer], cache.page_table,
                meta.attend_len, ring_axis=cfg.kv_ring_axis)
            out = project(out.reshape(B, T, H * D))
            out = lax.psum(out, cfg.tp_axis) if tp > 1 else out
            return out, cache
        if cfg.attention == "ring":
            out = seqpar.ring_attention(q, k, v, axis=cfg.seq_axis,
                                        causal=True)
        elif cfg.attention == "flash_ring":
            from ..ops.flash_attention import flash_ring_attention

            out = flash_ring_attention(q, k, v, axis=cfg.seq_axis,
                                       causal=True)
        elif cfg.attention == "ulysses":
            from ..ops.flash_attention import flash_attention

            out = seqpar.ulysses_attention(
                q, k, v, axis=cfg.seq_axis, causal=True,
                attn_fn=lambda qf, kf, vf: flash_attention(
                    qf, kf, vf, causal=True))
        elif cfg.attention == "flash":
            from ..ops.flash_attention import flash_attention

            out = flash_attention(q, k, v, causal=True)
        elif cfg.attention == "dense":
            out = seqpar.dense_attention(q, k, v, causal=True)
        else:
            raise ValueError(
                f"unknown attention {cfg.attention!r}; expected "
                f"dense | flash | ring | flash_ring | ulysses")
        out = project(out.reshape(B, T, H * D))
        # Row-parallel: each rank holds the rows for its heads; partial
        # results sum across the tp axis (biases are sliced 1/tp so the
        # psum restores the dense model's single bias).
        return lax.psum(out, cfg.tp_axis) if tp > 1 else out


class _MLP(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        tp = _tp_size(cfg)
        if cfg.d_ff % tp:
            raise ValueError(
                f"d_ff {cfg.d_ff} not divisible by tp axis size {tp}")
        x = nn.Dense(cfg.d_ff // tp, dtype=cfg.dtype,
                     kernel_init=nn.initializers.normal(0.02))(x)
        x = nn.gelu(x)
        x = nn.Dense(cfg.d_model, dtype=cfg.dtype,
                     kernel_init=nn.initializers.normal(
                         0.02 / (2 * cfg.num_layers) ** 0.5))(x)
        return lax.psum(x, cfg.tp_axis) if tp > 1 else x


class _FusedLNAdd(nn.Module):
    """Residual add + LayerNorm in one Pallas pass (ops/layer_norm.py).

    Param names/shapes match ``nn.LayerNorm`` exactly (scale, bias under
    this module's name) so dense checkpoints load into fused models and
    back."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, sub):
        from ..ops.layer_norm import ln_residual

        C = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (C,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (C,),
                          jnp.float32)
        # eps matches flax nn.LayerNorm's default (1e-6) so fused and
        # unfused models are numerically interchangeable.
        y, h = ln_residual(x, sub, scale, bias, 1e-6)
        return y.astype(self.cfg.dtype), h


class _Block(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, decode=None):
        cfg = self.cfg
        if decode is not None:
            # Decode path: same params, plain (unfused) pre-norm blocks —
            # fused_ln targets the [B, T, C] training stream and is
            # numerically interchangeable (identical eps/params), so a
            # T=1 decode never pays the Pallas call.
            cache, meta, layer = decode
            attn_out, cache = _Attention(cfg, name="attn")(
                _layer_norm(cfg, "ln1", x),
                decode=(cache, meta, layer))
            x = x + attn_out
            if cfg.moe_experts:
                from ..parallel.expert import SwitchMoE

                ffn = SwitchMoE(
                    num_experts=cfg.moe_experts, d_ff=cfg.d_ff,
                    capacity_factor=cfg.moe_capacity_factor,
                    ep_axis=cfg.ep_axis, dtype=cfg.dtype,
                    ragged=cfg.moe_ragged,
                    pair_capacity_factor=cfg.moe_pair_capacity_factor,
                    name="moe")
            else:
                ffn = functools.partial(_dense_mlp, cfg)
            x = x + ffn(_layer_norm(cfg, "ln2", x))
            return x, cache
        attn_out = _Attention(cfg, name="attn")(
            _layer_norm(cfg, "ln1", x))
        if not cfg.fused_ln:
            x = x + attn_out
        if cfg.moe_experts:
            from ..parallel.expert import SwitchMoE

            ffn = SwitchMoE(num_experts=cfg.moe_experts, d_ff=cfg.d_ff,
                            capacity_factor=cfg.moe_capacity_factor,
                            ep_axis=cfg.ep_axis, dtype=cfg.dtype,
                            ragged=cfg.moe_ragged,
                            pair_capacity_factor=cfg.moe_pair_capacity_factor,
                            name="moe")
        else:
            ffn = functools.partial(_dense_mlp, cfg)
        if cfg.fused_ln:
            # One pass: h = x + attn_out (the stream continues through
            # h), m = ln2(h) — the Pallas kernel's HBM saving.
            m, h = _FusedLNAdd(cfg, name="ln2")(x, attn_out)
            return h + ffn(m)
        x = x + ffn(_layer_norm(cfg, "ln2", x))
        return x


class GPT(nn.Module):
    """Decoder-only LM. Returns logits [B, T_local, vocab]; with
    ``cache=`` (a :class:`horovod_tpu.serve.kv_cache.KVCache`), runs one
    paged decode step instead — see :meth:`__call__`."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, tokens, cache=None, active=None):
        """Training/prefill forward, or — when ``cache`` is given — ONE
        continuous-batching decode step (serve/engine.py):

        ``tokens [S]`` (or ``[S, 1]``) holds the step's token per batch
        slot, written at position ``cache.seq_lens[s]`` of every layer's
        page pool; the returned logits ``[S, vocab]`` predict each slot's
        NEXT token, with attention over all cached positions including
        the one just written — so feeding a prompt token-by-token yields
        logits identical (within dtype tolerance) to the full-context
        forward at that position. ``active [S]`` bool masks dead slots
        (their writes hit the null page and their cursor stays put).
        Returns ``(logits, new_cache)``.
        """
        cfg = self.cfg
        if cache is not None:
            return self._decode_step(tokens, cache, active)
        B, T_local = tokens.shape
        wte = self.param("wte", nn.initializers.normal(cfg.embed_init_std),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(cfg.embed_init_std),
                         (cfg.max_seq_len, cfg.d_model), jnp.float32)
        if cfg.attention in ("ring", "flash_ring", "ulysses"):
            # Sequence is sharded: offset positions by the shard index.
            n_shards = seqpar._axis_size(cfg.seq_axis)
            pos = seqpar.seq_shard_positions(T_local, cfg.seq_axis)
        else:
            n_shards = 1
            pos = jnp.arange(T_local)
        if T_local * n_shards > cfg.max_seq_len:
            # JAX gathers clamp out-of-bounds indices under jit, which
            # would silently reuse the last positional embedding — fail
            # loudly instead.
            raise ValueError(
                f"global sequence length {T_local * n_shards} exceeds "
                f"max_seq_len={cfg.max_seq_len}")
        with jax.named_scope("hvd.embed"):
            x = (wte[tokens] + wpe[pos][None]).astype(cfg.dtype)
        block = _Block
        if cfg.remat:
            block = nn.remat(_Block)
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"h{i}")(x)
        x = _layer_norm(cfg, "ln_f", x)
        if cfg.return_hidden:
            return x
        # Tied embedding head. Inputs in the compute dtype (bf16 feeds the
        # MXU at full rate — the fp32 head matmul is ~18% of model FLOPs at
        # half throughput), accumulation and logits in fp32 for a stable
        # softmax.
        return jnp.einsum("btc,vc->btv", x, wte.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)

    def _decode_step(self, tokens, cache, active):
        from ..serve import kv_cache as kvlib

        cfg = self.cfg
        tp = _tp_size(cfg)
        if cfg.kv_ring_axis and cfg.tp_axis and tp > 1:
            ring = ({cfg.kv_ring_axis} if isinstance(cfg.kv_ring_axis, str)
                    else set(cfg.kv_ring_axis))
            tps = ({cfg.tp_axis} if isinstance(cfg.tp_axis, str)
                   else set(cfg.tp_axis))
            if ring & tps:
                raise ValueError(
                    f"kv_ring_axis {cfg.kv_ring_axis!r} overlaps tp_axis "
                    f"{cfg.tp_axis!r}: the page stripe would rotate "
                    f"between ranks holding different heads; use "
                    f"disjoint mesh axes")
        # Windowed step (speculative verify / chunked prefill): a 2-D
        # ``active [S, W]`` batches W tokens per slot through ONE apply.
        # Per-query attend lengths (``seq_lens + w + 1``) keep window
        # position w blind to positions > w, so the logits are
        # bit-identical to W chained single-token steps.
        windowed = active is not None and jnp.ndim(active) == 2
        if not windowed and tokens.ndim == 2:
            tokens = tokens[:, 0]
        S = tokens.shape[0]
        if active is None:
            active = jnp.ones((S,), bool)
        wte = self.param("wte", nn.initializers.normal(cfg.embed_init_std),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(cfg.embed_init_std),
                         (cfg.max_seq_len, cfg.d_model), jnp.float32)
        # One shared write cursor for every layer; the clip keeps the
        # embedding gather in-bounds on inactive slots (the engine bounds
        # live positions by max_seq_len/pages_per_slot at admission).
        meta = kvlib.step_meta(cache, active,
                               page_size=int(cache.k.shape[2]),
                               ring_axis=cfg.kv_ring_axis)
        with jax.named_scope("hvd.embed"):
            if windowed:
                W = tokens.shape[1]
                pos = jnp.clip(
                    cache.seq_lens[:, None] + jnp.arange(W)[None],
                    0, cfg.max_seq_len - 1)
                x = (wte[tokens] + wpe[pos]).astype(cfg.dtype)
            else:
                pos = jnp.clip(cache.seq_lens, 0, cfg.max_seq_len - 1)
                x = (wte[tokens] + wpe[pos]).astype(cfg.dtype)[:, None, :]
        block = _Block
        if cfg.remat:
            block = nn.remat(_Block)
        for i in range(cfg.num_layers):
            x, cache = block(cfg, name=f"h{i}")(x, decode=(cache, meta, i))
        x = _layer_norm(cfg, "ln_f", x)
        if windowed:
            logits = jnp.einsum("swc,vc->swv", x, wte.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("sc,vc->sv", x[:, 0],
                                wte.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
        return logits, kvlib.advance(cache, meta)


def gpt_small(**overrides) -> GPTConfig:
    """GPT-2-small scale (124M)."""
    return GPTConfig(**{**dict(num_layers=12, num_heads=12, d_model=768,
                               d_ff=3072), **overrides})


def gpt_tiny(**overrides) -> GPTConfig:
    """Test/dryrun scale."""
    return GPTConfig(**{**dict(vocab_size=128, num_layers=2, num_heads=4,
                               d_model=64, d_ff=128, max_seq_len=256),
                        **overrides})
