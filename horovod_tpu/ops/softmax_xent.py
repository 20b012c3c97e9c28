"""Fused linear cross-entropy: LM head matmul + softmax-xent, no logits.

The reference has no LM-head machinery at all (CNN-era framework); on TPU
the final ``hidden @ embeddingᵀ → softmax_cross_entropy`` chain is the
second HBM hog in an LM step after attention: at GPT-124M bench shapes
(N = 16·1024 tokens, V = 32000) the fp32 logits tensor is 2 GB — written
by the matmul, re-read by the softmax, regenerated and re-read in the
backward.

:func:`linear_cross_entropy` computes per-token
``loss_n = logsumexp_v(x_n · w_v) - x_n · w_{y_n}`` with Pallas kernels
that stream vocab blocks through VMEM (online logsumexp, same recipe as
flash attention's streaming softmax) and a custom VJP that recomputes the
blockwise softmax from the saved ``lse`` residual:

    dx_n = g_n · Σ_v (softmax_nv - 1[v = y_n]) · w_v
    dw_v = Σ_n g_n · (softmax_nv - 1[v = y_n]) · x_n

so HBM traffic is O(N·C + V·C) instead of O(N·V). Labels ride as an
(N, 8) int32 operand (broadcast sublane dim, Mosaic block-mapping
minimum); the one-hot is built in-kernel by comparing a vocab-position
iota against the label column.

Off-TPU the kernels run in Pallas interpreter mode (CPU test suite).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (
    _block_knob,
    _harmonize_vma,
    _interpret,
    _out_struct,
    _pick_block,
)

_NEG_INF = -1e30

# The dominant HBM cost is streaming the [V, C] weight matrix once per
# row block (it exceeds VMEM), so block_n is the lever: W traffic per
# kernel = (N / block_n) · V·C bytes — the larger the row block the
# better, until the backward no longer fits. What bounds it is the
# backward kernels' scoped VMEM (16 MiB on v5e): their fp32 [block, C]
# accumulators and [bn, bv] score temporaries grow with block·C. Asked
# of the v5e compiler (tests/test_tpu_lowering.py): bn·C = 512·1024
# compiles at C = 768, 1024 and 2048 where 1024·1024 (20.15M) and
# 512·2048 (19.17M) are refused; the vocab block tolerates 1.5× that
# (bv·C = 640·1024 and 384·2048 compile, 1024·1024 beside bn = 512 is
# refused at 16.24M).
_ROW_BLOCK_ELEMS = 512 * 1024


def _default_blocks(C: int):
    """(block_n, block_v) preferences for hidden width ``C``: the largest
    128-multiples (capped at 1024) whose backward stays inside scoped
    VMEM. ``_pick_block`` then snaps them to divisors of N and V."""
    def fit(elems):
        return max(128, min(1024, elems // C // 128 * 128))

    return fit(_ROW_BLOCK_ELEMS), fit(3 * _ROW_BLOCK_ELEMS // 2)


def _onehot_mask(labels_col, j, bn, bv):
    """[bn, bv] bool: vocab position == label (labels_col is [bn, 1])."""
    vpos = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    return vpos == labels_col


def _fwd_kernel(x_ref, w_ref, lab_ref, loss_ref, lse_ref,
                m_scr, l_scr, t_scr, *, bn, bv, nv):
    i = pl.program_id(0)   # token-row block
    j = pl.program_id(1)   # vocab block (innermost: scratch carries)
    del i

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        t_scr[:] = jnp.zeros_like(t_scr)

    # Body under a traced always-true pl.when: vma-mixed arithmetic
    # (unvarying scratch vs sharded operands) is only harmonized inside
    # cond branches by the HLO interpreter (see flash_attention._run_pred).
    @pl.when(j >= 0)
    def _body():
        s = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bn, bv]

        m_prev = m_scr[:, 0:1]
        l_prev = l_scr[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

        # Accumulate the label logit: exactly one vocab block contains it.
        hit = _onehot_mask(lab_ref[:, 0:1], j, bn, bv)
        t_scr[:] += jnp.broadcast_to(
            jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True),
            t_scr.shape)

    @pl.when(j == nv - 1)
    def _finish():
        lse = m_scr[:, 0:1] + jnp.log(l_scr[:, 0:1])
        loss_ref[...] = jnp.broadcast_to(lse - t_scr[:, 0:1],
                                         loss_ref.shape)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _bwd_dx_kernel(x_ref, w_ref, lab_ref, lse_ref, g_ref, dx_ref, acc_scr,
                   *, bn, bv, nv):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j >= 0)
    def _body():
        s = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bn, bv]
        p = jnp.exp(s - lse_ref[:, 0:1])               # softmax block
        hit = _onehot_mask(lab_ref[:, 0:1], j, bn, bv)
        ds = (p - hit.astype(jnp.float32)) * g_ref[:, 0:1]
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(w_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bn, C]

    @pl.when(j == nv - 1)
    def _finish():
        dx_ref[...] = acc_scr[:].astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, w_ref, lab_ref, lse_ref, g_ref, dw_ref, acc_scr,
                   *, bn, bv, nn):
    j = pl.program_id(0)   # vocab block
    i = pl.program_id(1)   # token block (innermost: scratch carries)

    @pl.when(i == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(i >= 0)
    def _body():
        s = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bn, bv]
        p = jnp.exp(s - lse_ref[:, 0:1])
        hit = _onehot_mask(lab_ref[:, 0:1], j, bn, bv)
        ds = (p - hit.astype(jnp.float32)) * g_ref[:, 0:1]
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(x_ref.dtype), x_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bv, C]

    @pl.when(i == nn - 1)
    def _finish():
        dw_ref[...] = acc_scr[:].astype(dw_ref.dtype)


def _broadcast8(x, dtype=None):
    x = jnp.asarray(x)
    if dtype is not None:
        x = x.astype(dtype)
    return jnp.broadcast_to(x[:, None], (*x.shape, 8))


def _xent_fwd(x, w, labels8, bn, bv):
    N, C = x.shape
    V = w.shape[0]
    nn, nv = N // bn, V // bv
    loss8, lse8 = pl.pallas_call(
        functools.partial(_fwd_kernel, bn=bn, bv=bv, nv=nv),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, C), lambda i, j: (i, 0)),     # x
            pl.BlockSpec((bv, C), lambda i, j: (j, 0)),     # w
            pl.BlockSpec((bn, 8), lambda i, j: (i, 0)),     # labels
        ],
        out_specs=[
            pl.BlockSpec((bn, 8), lambda i, j: (i, 0)),     # loss
            pl.BlockSpec((bn, 8), lambda i, j: (i, 0)),     # lse
        ],
        out_shape=[
            _out_struct((N, 8), jnp.float32, x, w, labels8),
            _out_struct((N, 8), jnp.float32, x, w, labels8),
        ],
        scratch_shapes=[pltpu.VMEM((bn, 128), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="hvd_xent_fwd",
    )(x, w, labels8)
    return loss8[:, 0], lse8


def _xent_bwd(x, w, labels8, lse8, g8, bn, bv):
    N, C = x.shape
    V = w.shape[0]
    nn, nv = N // bn, V // bv
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, bn=bn, bv=bv, nv=nv),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, C), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, C), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 8), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 8), lambda i, j: (i, 0)),     # lse
            pl.BlockSpec((bn, 8), lambda i, j: (i, 0)),     # g
        ],
        out_specs=pl.BlockSpec((bn, C), lambda i, j: (i, 0)),
        out_shape=_out_struct((N, C), x.dtype, x, w, labels8, lse8, g8),
        scratch_shapes=[pltpu.VMEM((bn, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="hvd_xent_bwd_dx",
    )(x, w, labels8, lse8, g8)

    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, bn=bn, bv=bv, nn=nn),
        grid=(nv, nn),
        in_specs=[
            pl.BlockSpec((bn, C), lambda j, i: (i, 0)),
            pl.BlockSpec((bv, C), lambda j, i: (j, 0)),
            pl.BlockSpec((bn, 8), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, 8), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, 8), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bv, C), lambda j, i: (j, 0)),
        out_shape=_out_struct((V, C), w.dtype, x, w, labels8, lse8, g8),
        scratch_shapes=[pltpu.VMEM((bv, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="hvd_xent_bwd_dw",
    )(x, w, labels8, lse8, g8)
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _linear_xent(x, w, labels8, bn, bv):
    loss, _ = _xent_fwd(x, w, labels8, bn, bv)
    return loss


def _linear_xent_vjp_fwd(x, w, labels8, bn, bv):
    loss, lse8 = _xent_fwd(x, w, labels8, bn, bv)
    return loss, (x, w, labels8, lse8)


def _linear_xent_vjp_bwd(bn, bv, res, g):
    x, w, labels8, lse8 = res
    dx, dw = _xent_bwd(x, w, labels8, lse8, _broadcast8(g, jnp.float32),
                       bn, bv)
    return dx, dw, None


_linear_xent.defvjp(_linear_xent_vjp_fwd, _linear_xent_vjp_bwd)


def _dense_xent(x, w, labels, dtype=None):
    """The plain XLA formulation: einsum head + optax cross-entropy.
    Single source for both linear_cross_entropy's no-legal-blocking
    fallback and lm_head_loss's dense branch."""
    import optax

    logits = jnp.einsum("...c,vc->...v",
                        x if dtype is None else x.astype(dtype),
                        w if dtype is None else w.astype(dtype),
                        preferred_element_type=jnp.float32)
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


def linear_cross_entropy(x, w, labels, *,
                         block_n=None,
                         block_v=None):
    """Per-token cross entropy of ``softmax(x @ wᵀ)`` against ``labels``.

    ``x``: [..., C] activations (any leading shape); ``w``: [V, C] vocab
    embedding/head matrix; ``labels``: [...] int. Returns [...] fp32
    losses. Differentiable w.r.t. ``x`` and ``w`` (custom VJP, Pallas
    kernels; the [N, V] logits never touch HBM). Falls back to the plain
    XLA formulation when no legal blocking exists.

    Blocks default to the kernel autotuner's cached/swept choice for this
    (shape, chip) (ops/kernel_autotune.py), and off-TPU or with the sweep
    off to :func:`_default_blocks` — derived from ``C`` so the backward
    fits scoped VMEM — unless the ``HOROVOD_XENT_BLOCK_N/V`` knobs or
    explicit arguments pin them.
    """
    import os

    lead = x.shape[:-1]
    C = x.shape[-1]
    V = w.shape[0]
    N = 1
    for d in lead:
        N *= d
    xf = x.reshape(N, C)
    lab = labels.reshape(N)
    def_n, def_v = _default_blocks(C)
    # Knobs are read at CALL time so a runtime os.environ override works;
    # an empty string means unset (the shell idiom _env_int honors).
    pinned = bool(os.environ.get("HOROVOD_XENT_BLOCK_N")
                  or os.environ.get("HOROVOD_XENT_BLOCK_V"))
    knob_n = _block_knob("HOROVOD_XENT_BLOCK_N", def_n)
    knob_v = _block_knob("HOROVOD_XENT_BLOCK_V", def_v)
    if block_n is None and block_v is None:
        from . import kernel_autotune

        if pinned or not kernel_autotune.enabled():
            block_n, block_v = knob_n, knob_v
        else:
            block_n, block_v = kernel_autotune.xent_blocks(
                N, V, C, x.dtype, (def_n, def_v), _pick_block)
    else:
        block_n = knob_n if block_n is None else block_n
        block_v = knob_v if block_v is None else block_v
    bn, bv = _pick_block(N, block_n), _pick_block(V, block_v)
    if bn is None or bv is None:
        return _dense_xent(xf, w, lab, dtype=jnp.float32).reshape(lead)
    xf, w, lab8 = _harmonize_vma(xf, w, _broadcast8(lab, jnp.int32))
    loss = _linear_xent(xf, w, lab8, bn, bv)
    return loss.reshape(lead)


def lm_head_loss(x, w, labels, *, mode: str = "auto"):
    """LM-head loss with measured dispatch: XLA's dense einsum+optax head
    wherever its logits fit, the fused Pallas kernel beyond.

    Measured on one v5e (GPT-124M step, seq 1024, per-chip batch 8;
    round 4, not re-measured): the dense head is uniformly FASTER at
    every vocab that compiles — 110.4k vs 105.2k tok/s at V=32k, 94.5k
    vs 90.8k at 64k, 76.5k vs 70.5k at 128k, 55.4k vs 49.2k at 256k
    (4–11%; XLA's
    fused matmul+xent is near-roofline and its [N, V] round trip is
    cheaper than this kernel's extra W re-streams). There is NO
    throughput crossover: the fused kernel's value is the operating
    envelope — at [32k tokens x 128k vocab] the dense step fails to
    compile (the fp32 logits alone are 17 GB against 16 GB HBM) while
    the fused path runs. ``mode="auto"`` therefore picks dense while a
    single fp32 logits buffer (``N * V * 4`` bytes — the unit XLA must
    materialize at least once in the dense head) stays under
    ``HOROVOD_XENT_AUTO_LOGITS_GB`` (default 10 GiB: strictly above the
    measured-working 256k point, which is exactly 8 GiB, so that point
    stays dense with margin rather than by strict-inequality luck; and
    safely below the failing 17 GB point), and fused above it.
    ``mode="dense"``/``"fused"`` force a path.
    """
    import os

    if mode not in ("auto", "dense", "fused"):
        raise ValueError(f"mode must be auto|dense|fused, got {mode!r}")
    use_fused = mode == "fused"
    if mode == "auto":
        N = 1
        for d in x.shape[:-1]:
            N *= d
        budget = float(os.environ.get(
            "HOROVOD_XENT_AUTO_LOGITS_GB", "10")) * 2 ** 30
        use_fused = N * w.shape[0] * 4.0 > budget
    with jax.named_scope("hvd.lm_head_loss"):
        if use_fused:
            return linear_cross_entropy(x, w, labels)
        return _dense_xent(x, w, labels)
