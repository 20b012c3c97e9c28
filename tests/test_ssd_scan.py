"""``hvd.ssd_scan`` (ops/ssd_scan.py): Mamba-2's chunked scan against the
recurrence token by token: values and all six gradients at chunk sizes that
do and do not divide ``T``, heads over one and several groups, the state
carried across chunk boundaries, float32 states whatever the operands',
what a rematerialised caller keeps, inside ``shard_map``, and no array of a
state a token anywhere in the differentiated program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.monitor.registry import counter
from horovod_tpu.ops import ssd_scan as S

NAMES = ("xs", "dt", "A_log", "B", "C", "Dskip")
#: (B, T, h, P, G, N, chunk): several whole chunks, one group; a T no chunk
#: divides, two groups; ONE chunk, a group a head; a last chunk of 8 of 16.
SHAPES = [(2, 64, 4, 8, 1, 16, 16), (1, 50, 4, 8, 2, 16, 16),
          (1, 32, 4, 8, 4, 8, 32), (2, 40, 2, 16, 1, 16, 16)]


def operands(B, T, h, Pd, G, N, seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)

    def arr(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.float32)

    return (arr(B, T, h, Pd).astype(dtype),
            jax.nn.softplus(arr(B, T, h) - 2),
            jnp.log(jnp.asarray(rs.uniform(1, 16, (h,)), jnp.float32)),
            arr(B, T, G, N).astype(dtype), arr(B, T, G, N).astype(dtype),
            arr(h))


def token_by_token(xs, dt, A_log, Bm, Cm, Dskip):
    """The recurrence written out with numpy loops over tokens, float64."""
    xs, dt, A_log, Bm, Cm, Dskip = (np.asarray(a, np.float64) for a in
                                    (xs, dt, A_log, Bm, Cm, Dskip))
    B, T, h, Pd = xs.shape
    G, N = Bm.shape[2:]
    group = np.arange(h) // (h // G)
    y, S_ = np.zeros((B, T, h, Pd)), np.zeros((B, h, Pd, N))
    for t in range(T):
        a = np.exp(-np.exp(A_log) * dt[:, t])                    # [B, h]
        S_ = a[:, :, None, None] * S_ + (
            dt[:, t, :, None] * xs[:, t])[..., None] \
            * Bm[:, t, group][:, :, None, :]
        y[:, t] = (S_ * Cm[:, t, group][:, :, None, :]).sum(-1) \
            + Dskip[:, None] * xs[:, t]
    return y


def rel_gap(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_values_are_the_token_by_token_scans(shape):
    *sizes, chunk = shape
    ops = operands(*sizes)
    want = token_by_token(*ops)
    chunks = counter("ssd.chunks")
    before = chunks.value
    with jax.default_matmul_precision("highest"):
        got = hvd.ssd_scan(*ops, chunk=chunk)
        ref = S.ssd_scan_reference(*ops)
    B, T = sizes[:2]
    assert chunks.value - before == B * -(-T // chunk)
    assert got.shape == ops[0].shape and got.dtype == jnp.float32
    # float32 sums in another order than the loop's: 1e-5 of the largest
    assert rel_gap(got, want) < 1e-5 and rel_gap(ref, want) < 1e-5


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_all_six_gradients_are_the_scans(shape):
    *sizes, chunk = shape
    ops = operands(*sizes, seed=1)
    w = jnp.asarray(np.random.RandomState(2).randn(*ops[0].shape),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: (hvd.ssd_scan(*a, chunk=chunk) * w).sum(),
                       argnums=range(6))(*ops)
        want = jax.grad(lambda *a: (S.ssd_scan_reference(*a) * w).sum(),
                        argnums=range(6))(*ops)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel_gap(a, b) < 2e-5, name


def test_the_state_crosses_every_chunk_boundary():
    """An input at token 0 alone is read through C at the LAST token: what
    comes out there went through every chunk's state."""
    B, T, h, Pd, G, N = 1, 64, 2, 4, 1, 8
    xs, dt, A_log, Bm, Cm, Dskip = operands(B, T, h, Pd, G, N, seed=3)
    xs = xs.at[:, 1:].set(0.0)
    A_log = jnp.full((h,), -3.0)          # a slow decay: the state survives
    ops = (xs, dt, A_log, Bm, Cm, jnp.zeros((h,)))
    with jax.default_matmul_precision("highest"):
        got = hvd.ssd_scan(*ops, chunk=8)
    want = token_by_token(*ops)
    assert float(np.abs(want[:, -1]).max()) > 1e-3
    assert rel_gap(got[:, -1], want[:, -1]) < 1e-5


def test_bfloat16_operands_keep_float32_decays_and_states():
    """bfloat16 operands round the matmuls' inputs once each (4e-3 a
    product) and nothing the state carries: the output stays within 2e-2 of
    the float32 scan over 16 chunks, and comes back in the operands' type
    with gradients in theirs."""
    ops = operands(1, 256, 4, 8, 2, 16, seed=4, dtype=jnp.bfloat16)
    got = hvd.ssd_scan(*ops, chunk=16)
    assert got.dtype == jnp.bfloat16
    want = token_by_token(*(a.astype(jnp.float32) for a in ops))
    assert rel_gap(got.astype(jnp.float32), want) < 2e-2
    grads = jax.grad(lambda *a: hvd.ssd_scan(*a, chunk=16).astype(
        jnp.float32).sum(), argnums=range(6))(*ops)
    assert [g.dtype for g in grads] == [a.dtype for a in ops]


def test_no_state_a_token_in_the_differentiated_program():
    B, T, h, Pd, G, N = 1, 64, 2, 4, 1, 8
    ops = operands(B, T, h, Pd, G, N)
    text = jax.jit(jax.grad(lambda *a: hvd.ssd_scan(*a, chunk=16).sum(),
                            argnums=range(6))).lower(*ops).as_text()
    assert f"{T}x{h}x{Pd}x{N}x" not in text       # [T, h, P, N]
    assert f"{T // 16}x{G}x{h // G}x{Pd}x{N}x" in text   # a state a chunk


def test_kept_under_remat_the_backward_runs_no_second_forward():
    """Under ``save_only_these_names(OUT_NAME)`` the output and the chunk
    states are residuals: the differentiated program holds the forward's
    matmuls once (three of the chunk's and the recurrence's), not twice."""
    ops = operands(1, 64, 2, 4, 1, 8)
    policy = jax.checkpoint_policies.save_only_these_names(S.OUT_NAME)

    def loss(*a):
        return jax.checkpoint(
            lambda *b: hvd.ssd_scan(*b, chunk=16), policy=policy)(*a).sum()

    def dots(fn):
        return str(jax.make_jaxpr(jax.grad(fn, argnums=range(6)))(*ops)) \
            .count("dot_general")

    plain = dots(lambda *a: hvd.ssd_scan(*a, chunk=16).sum())
    assert dots(loss) == plain
    unkept = dots(lambda *a: jax.checkpoint(
        lambda *b: hvd.ssd_scan(*b, chunk=16),
        policy=jax.checkpoint_policies.nothing_saveable)(*a).sum())
    assert unkept > plain


def test_inside_shard_map_every_rank_scans_its_rows():
    n = len(jax.devices())
    ops = operands(n, 32, 2, 4, 1, 8, seed=5)
    w = jnp.asarray(np.random.RandomState(6).randn(*ops[0].shape),
                    jnp.float32)

    def body(xs, dt, A_log, Bm, Cm, Dskip, w):
        loss, grads = jax.value_and_grad(
            lambda *a: (hvd.ssd_scan(*a, chunk=8) * w).sum(),
            argnums=range(6))(xs, dt, A_log, Bm, Cm, Dskip)
        return hvd.allreduce(loss, op=hvd.Sum), grads[2]

    data, rep = hvd.data_pspec(), P()
    with jax.default_matmul_precision("highest"):
        loss, d_a = jax.jit(hvd.shard_map(
            body, mesh=hvd.mesh(),
            in_specs=(data, data, rep, data, data, rep, data),
            out_specs=(rep, rep)))(*ops, w)
        want, want_a = jax.value_and_grad(
            lambda a: (S.ssd_scan_reference(ops[0], ops[1], a, *ops[3:])
                       * w).sum())(ops[2])
    assert abs(float(loss) - float(want)) < 1e-4 * abs(float(want))
    # a replicated operand's gradient comes back summed over the ranks
    assert rel_gap(d_a, want_a) < 2e-5


@pytest.mark.parametrize("bad", ["groups", "dt", "chunk"])
def test_shapes_that_cannot_be_a_scan_are_refused(bad):
    xs, dt, A_log, Bm, Cm, Dskip = operands(1, 16, 4, 4, 2, 8)
    if bad == "groups":
        Bm, Cm = (jnp.zeros((1, 16, 3, 8)),) * 2
    if bad == "dt":
        dt = dt[..., :2]
    with pytest.raises(ValueError):
        hvd.ssd_scan(xs, dt, A_log, Bm, Cm, Dskip,
                     chunk=0 if bad == "chunk" else 8)
