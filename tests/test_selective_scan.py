"""``hvd.selective_scan`` (ops/selective_scan.py): the Pallas kernels (in the
interpreter here) and the fallback against a token-by-token ``lax.scan``:
values and all six gradients, several chunks, channels that fill no whole
block, the state carried across a chunk boundary, and no array of a state a
token anywhere in the differentiated program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.monitor.registry import counter
from horovod_tpu.ops import kernel_autotune
from horovod_tpu.ops import selective_scan as S

NAMES = ("x", "dt", "A", "B", "C", "Dskip")
#: (B, T, Dn, N, chunk): several chunks with channels that are no block
#: multiple; one chunk of a whole block; a state count with another chunk.
SHAPES = [(2, 192, 200, 16, 64), (1, 64, 1024, 16, 64),
          (1, 256, 128, 8, 128)]


def operands(B, T, Dn, N, seed=0):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(B, T, Dn), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rs.randn(B, T, Dn), jnp.float32) - 2)
    A = -jnp.exp(jnp.asarray(rs.randn(Dn, N) * 0.5, jnp.float32))
    Bm, Cm = (jnp.asarray(rs.randn(B, T, N), jnp.float32) for _ in "bc")
    return x, dt, A, Bm, Cm, jnp.asarray(rs.randn(Dn), jnp.float32)


def token_by_token(x, dt, A, Bm, Cm, Dskip):
    """The recurrence written out with numpy loops over tokens, float64."""
    x, dt, A, Bm, Cm, Dskip = (np.asarray(a, np.float64)
                               for a in (x, dt, A, Bm, Cm, Dskip))
    B, T, Dn = x.shape
    y, h = np.zeros((B, T, Dn)), np.zeros((B, Dn, A.shape[1]))
    for t in range(T):
        h = np.exp(dt[:, t, :, None] * A) * h \
            + (dt[:, t] * x[:, t])[:, :, None] * Bm[:, t, None, :]
        y[:, t] = (h * Cm[:, t, None, :]).sum(-1) + Dskip * x[:, t]
    return y


def weighted(fn, w, **kw):
    return lambda *ops: (fn(*ops, **kw) * w).sum()


def rel_gap(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_values_are_the_token_by_token_scans(shape):
    *sizes, chunk = shape
    ops = operands(*sizes)
    want = token_by_token(*ops)
    before = counter("ssm.scan_path", path="kernel").value
    got = hvd.selective_scan(*ops, chunk=chunk, block_d=1024)
    assert counter("ssm.scan_path", path="kernel").value == before + 1
    assert got.dtype == jnp.float32 and got.shape == ops[0].shape
    assert rel_gap(got, want) < 1e-5
    assert rel_gap(S.selective_scan_reference(*ops), want) < 1e-5


def test_the_state_crosses_a_chunk_boundary():
    """One chunk of 128 and two of 64 give the same output, and the second
    half depends on the first half's inputs."""
    ops = operands(1, 128, 128, 16, seed=3)
    one = hvd.selective_scan(*ops, chunk=128, block_d=1024)
    two = hvd.selective_scan(*ops, chunk=64, block_d=1024)
    np.testing.assert_allclose(one, two, rtol=1e-6, atol=1e-6)
    moved = hvd.selective_scan(ops[0].at[0, 10].add(1.0), *ops[1:],
                               chunk=64, block_d=1024)
    assert float(jnp.abs(moved - two)[0, 64:].max()) > 1e-4


def test_a_sequence_no_chunk_divides_takes_the_fallback():
    ops = operands(1, 40, 128, 16, seed=4)
    before = counter("ssm.scan_path", path="fallback").value
    got = hvd.selective_scan(*ops)
    assert counter("ssm.scan_path", path="fallback").value == before + 1
    assert rel_gap(got, token_by_token(*ops)) < 1e-5
    grads = jax.grad(weighted(hvd.selective_scan, 1.0), (2, 5))(*ops)
    want = jax.grad(weighted(S.selective_scan_reference, 1.0), (2, 5))(*ops)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_more_states_than_the_registers_hold_take_the_fallback():
    ops = operands(1, 64, 128, S.MAX_STATES * 2, seed=5)
    before = counter("ssm.scan_path", path="fallback").value
    hvd.selective_scan(*ops)
    assert counter("ssm.scan_path", path="fallback").value == before + 1


def _shapes_in(jaxpr, found):
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape is not None:
                found.add(tuple(shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes_in(sub, found)
    return found


def test_no_state_a_token_in_the_differentiated_program():
    B, T, Dn, N = 1, 128, 2048, 16
    ops = operands(B, T, Dn, N)
    every = tuple(range(6))
    kernel = jax.make_jaxpr(jax.grad(weighted(
        hvd.selective_scan, 1.0, chunk=64, block_d=1024), every))(*ops)
    sizes = {int(np.prod(s)) for s in _shapes_in(kernel.jaxpr, set())}
    assert max(sizes) < T * Dn * N
    # The fallback's has one: the check can see it.
    plain = jax.make_jaxpr(jax.grad(weighted(
        S.selective_scan_reference, 1.0), every))(*ops)
    assert max(int(np.prod(s)) for s in
               _shapes_in(plain.jaxpr, set())) >= T * Dn * N


def test_the_output_is_rounded_once_and_gradients_keep_their_types():
    ops = operands(1, 64, 128, 16, seed=6)
    ops = (ops[0].astype(jnp.bfloat16),) + ops[1:]
    full = hvd.selective_scan(*ops, chunk=64)
    low = hvd.selective_scan(*ops, out_dtype=jnp.bfloat16, chunk=64)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_array_equal(low, full.astype(jnp.bfloat16))
    grads = jax.grad(lambda *o: hvd.selective_scan(
        *o, out_dtype=jnp.bfloat16, chunk=64).astype(jnp.float32).sum(),
        (0, 1))(*ops)
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32]


def test_kept_under_remat_the_forward_kernel_runs_once():
    ops = operands(1, 64, 128, 16, seed=7)

    def block(*o):
        return jnp.tanh(hvd.selective_scan(*o, chunk=64)).sum()

    policy = jax.checkpoint_policies.save_only_these_names(S.OUT_NAME)
    text = str(jax.make_jaxpr(jax.grad(jax.checkpoint(
        block, policy=policy), (0, 2)))(*ops))
    assert text.count("name=hvd_selective_scan_fwd") == 1
    assert text.count("name=hvd_selective_scan_bwd") == 1
    again = str(jax.make_jaxpr(jax.grad(jax.checkpoint(block), (0, 2)))(
        *ops))
    assert again.count("name=hvd_selective_scan_fwd") == 2


@pytest.mark.parametrize("T, preferred, N, chunk", [
    (8192, 64, 16, 64), (8192, 256, 16, 256), (192, 128, 16, 64),
    (96, 64, 16, None), (256, 64, 8, None), (256, 128, 8, 128)])
def test_a_chunk_divides_the_sequence_and_fills_scalar_tiles(T, preferred,
                                                            N, chunk):
    assert S.pick_chunk(T, preferred, N) == chunk


def test_counters_say_what_is_kept_for_the_backward():
    ops = operands(2, 128, 200, 16)
    chunks, state = (counter("ssm.scan_chunks").value,
                     counter("ssm.state_bytes").value)
    hvd.selective_scan(*ops, chunk=64, block_d=1024)
    assert counter("ssm.scan_chunks").value - chunks == 2 * 2
    # Two sequences x two chunks x 16 states x 1024 padded channels, float32.
    assert counter("ssm.state_bytes").value - state == 2 * 2 * 16 * 1024 * 4


def test_a_channel_block_is_whole_registers():
    with pytest.raises(ValueError, match="multiple of 1024"):
        hvd.selective_scan(*operands(1, 64, 128, 16), block_d=512)


def test_the_sweep_times_legal_blockings_once_each(monkeypatch):
    """Off-TPU nothing is swept; on one, the candidates are those whose
    chunk is legal for the shape, deduplicated, forward and backward."""
    assert kernel_autotune.scan_blocks(
        1, 8192, 5120, 16, S.DEFAULT_BLOCKS, S.CANDIDATES,
        S.pick_chunk) == S.DEFAULT_BLOCKS
    seen = {}

    def fake(kind, sig, cands, bench, default):
        seen.update(kind=kind, sig=sig, cands=list(cands))
        return cands[-1]

    monkeypatch.setattr(kernel_autotune, "get_or_tune", fake)
    assert kernel_autotune.scan_blocks(
        1, 192, 5120 * 64, 16, S.DEFAULT_BLOCKS, S.CANDIDATES,
        S.pick_chunk) == (64, 2048)
    # At T = 192 every chunk snaps to 64: one candidate a channel block.
    assert seen["cands"] == [(64, 1024), (64, 2048)]
    assert seen["kind"] == "selective_scan" and "T192" in seen["sig"]
    # A toy scan is not swept at all.
    seen.clear()
    kernel_autotune.scan_blocks(1, 64, 128, 16, S.DEFAULT_BLOCKS,
                                S.CANDIDATES, S.pick_chunk)
    assert not seen


def _gradient_cases():
    """(B, T, Dn, N, chunk, block_d): ``SHAPES``; then, for the backward
    kernel, every candidate blocking that divides T = 512 at 16 states, 8
    and 32 states, three registers of channels: B = 2, the channels fill no
    block, and a sequence is at least four chunks, so that ``a g`` crosses
    chunk boundaries and dA / dDskip are added to more than once."""
    T = 512
    return ([(*shape, 1024) for shape in SHAPES]
            + [(2, T, 1300, 16, chunk, block_d)
               for chunk, block_d in S.CANDIDATES
               if S.pick_chunk(T, chunk, 16) == chunk]
            + [(2, T, 1300, 8, 128, 1024), (2, 256, 1300, 32, 64, 1024),
               (2, 256, 2100, 16, 64, 1024)])


@pytest.mark.parametrize("case", _gradient_cases(), ids=str)
def test_all_six_gradients_are_the_scans(case):
    *sizes, chunk, block_d = case
    ops = operands(*sizes, seed=1)
    w = jnp.asarray(np.random.RandomState(2).randn(*ops[0].shape),
                    jnp.float32)
    every = tuple(range(6))
    got = jax.jit(jax.grad(weighted(hvd.selective_scan, w, chunk=chunk,
                                    block_d=block_d), every))(*ops)
    want = jax.jit(jax.grad(weighted(S.selective_scan_reference, w),
                            every))(*ops)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel_gap(a, b) < 1e-5, name


def test_three_bfloat16_pieces_are_the_float32():
    """What the backward's one-pass matmuls sum: each piece survives a
    rounding to bfloat16 and the three add up to the value, bit for bit."""
    v = jnp.asarray(np.random.RandomState(11).randn(8, 128) * 1e3,
                    jnp.float32)
    pieces = S._bf16_pieces(v)
    for piece in pieces:
        np.testing.assert_array_equal(
            piece, piece.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(pieces[0] + pieces[1] + pieces[2], v)


def test_the_differentiated_program_has_one_backward_kernel_a_scan():
    """Whatever the backward does a chunk, it is ONE kernel under the name
    the benchmark's reader takes events by (a second one under another
    name would put part of the time outside the roofline's share)."""
    ops = operands(1, 128, 2100, 16, seed=10)

    def two(*o):
        y = hvd.selective_scan(*o, chunk=64, block_d=1024)
        return hvd.selective_scan(y, *o[1:], chunk=64, block_d=1024).sum()

    text = str(jax.make_jaxpr(jax.grad(two, tuple(range(6))))(*ops))
    assert text.count("name=hvd_selective_scan_bwd") == 2
    assert text.count("name=hvd_selective_scan_fwd") == 2
    assert text.count("pallas_call[") == 4


def test_a_backward_grid_step_takes_the_channels_its_vmem_holds():
    """All of the cell's 40 rows of channels a step at chunk 64; one
    register where a chunk's sums leave no room; a divisor of the rows."""
    assert S.bwd_registers(40, 64, 16) == 5
    assert S.bwd_registers(40, 128, 16) == 1
    assert S.bwd_registers(8, 64, 16) == 1
    assert S.bwd_registers(128, 64, 16) == 8
    assert S.bwd_registers(24, 64, 32) == 3
