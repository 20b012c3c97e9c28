"""``ops/diff_attention.py``: the query laid into its half of a wide head and
the pair's difference with its norm, against the expressions
``models/sambay.py`` held before them (kept here as the reference): values
and every cotangent, the kernels' bodies in the Pallas interpreter against
the ``jax.numpy`` path over blocks of rows that the sequence does not fill,
and the path counted once a call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.monitor.registry import counter
from horovod_tpu.ops import diff_attention as DA

#: (H, D): a narrow head, the cell's, and an odd number of pairs.
HEADS = [(4, 8), (40, 64), (6, 64)]
DTYPES = [jnp.bfloat16, jnp.float32]
B, T = 2, 40            # T fills no whole block of 16 or 32 rows
EPS = 1e-5


def parent_lay(q, D):
    """``_DiffAttention``'s expression before PR 43, under
    ``lay_in_halves``' signature."""
    b, t, c = q.shape
    q = q.reshape(b, t, c // (2 * D), 2, 1, D)
    return (q * jnp.eye(2, dtype=q.dtype)[:, :, None]).reshape(b, t, 2 * c)


def parent_combine(o, lam, scale, eps):
    """``_DiffAttention``'s expression before PR 43, its norm
    (``sparse_moe_decoder.rms_norm_in_scope``) written out, under
    ``diff_combine``'s signature."""
    b, t, c = o.shape
    W = scale.shape[0]
    o5 = o.reshape(b, t, c // (2 * W), 2, W).astype(jnp.float32)
    a = o5[:, :, :, 0] - lam * o5[:, :, :, 1]
    y = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps)
    return (y * scale).astype(o.dtype).reshape(b, t, c // 2)


def operands(H, D, dtype, seed=0):
    rs = np.random.RandomState(seed)
    W = 2 * D
    return dict(
        q=jnp.asarray(rs.randn(B, T, H * D), dtype),
        o=jnp.asarray(rs.randn(B, T, H * W), dtype),
        g_wide=jnp.asarray(rs.randn(B, T, H * W), dtype),
        g=jnp.asarray(rs.randn(B, T, H * D), dtype),
        lam=jnp.float32(0.37),
        scale=jnp.asarray(rs.rand(W) + 0.5, jnp.float32))


def same_values(got, want):
    """Equal as values and in type (a zero's sign apart: ``x * 0`` is -0
    for a negative x, a mask writes +0, and no sum tells them apart)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def close(got, want, dtype=jnp.float32):
    """Within float32 rounding of a 128-term sum of the reference's
    largest entry; a bfloat16 result may besides round the other way."""
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 0.0
    np.testing.assert_allclose(got, want, rtol=ulp,
                               atol=2e-5 * np.abs(want).max())


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """The kernel path off the TPU: the calls' own switch says "no
    interpreter", and every ``pallas_call`` runs in it all the same, over
    blocks of 32 rows (two trips of a kernel's loop, the last block 8 rows
    of the sequence)."""
    call = DA._call

    def interpreted(*args):
        return call(*args[:-2], 32, True)

    monkeypatch.setattr(DA, "_call", interpreted)
    monkeypatch.setattr(DA, "_interpret", lambda: False)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("H, D", HEADS)
def test_lay_in_halves_is_the_parents_expression(H, D, dtype):
    ops = operands(H, D, dtype)
    want, pull = jax.vjp(lambda q: parent_lay(q, D), ops["q"])
    got, mine = jax.vjp(lambda q: DA.lay_in_halves(q, D), ops["q"])
    same_values(got, want)
    same_values(mine(ops["g_wide"])[0], pull(ops["g_wide"])[0])
    # head 2p + e: its D values in half e, zeros in the other
    heads = np.asarray(got, np.float32).reshape(B, T, H, 2, D)
    q = np.asarray(ops["q"], np.float32).reshape(B, T, H, D)
    for e in (0, 1):
        np.testing.assert_array_equal(heads[:, :, e::2, e], q[:, :, e::2])
        np.testing.assert_array_equal(heads[:, :, e::2, 1 - e], 0.0)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("H, D", HEADS)
def test_diff_combine_is_the_parents_expression(H, D, dtype):
    ops = operands(H, D, dtype, seed=1)
    args = ops["o"], ops["lam"], ops["scale"]
    want, pull = jax.vjp(lambda *a: parent_combine(*a, EPS), *args)
    got, mine = jax.vjp(lambda *a: DA.diff_combine(*a, EPS), *args)
    close(got, want, dtype)
    for name, a, b in zip(("do", "dlam", "dscale"), mine(ops["g"]),
                          pull(ops["g"])):
        assert a.dtype == b.dtype, name
        if name == "do":
            close(a, b, dtype)
        else:                  # sums of B * T * H / 2 * 2 D terms each
            np.testing.assert_allclose(a, b, rtol=1e-4,
                                       atol=1e-4 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("block_rows", [16, 32])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("H, D", HEADS)
def test_the_kernels_bodies_are_the_jnp_path(H, D, dtype, block_rows):
    """Each of the four kernels in the interpreter, over blocks the
    sequence does not fill (one trip of a kernel's loop over its rows, and
    two), against the path the CPU takes."""
    ops = operands(H, D, dtype, seed=2)
    run = dict(block_rows=block_rows, interpret=True)
    same_values(DA._lay_fwd_call(ops["q"], D, **run),
                DA._lay_fwd_xla(ops["q"], D))
    same_values(DA._lay_bwd_call(ops["g_wide"], D, **run),
                DA._lay_bwd_xla(ops["g_wide"], D))
    args = ops["lam"], ops["scale"], EPS
    close(DA._combine_fwd_call(ops["o"], *args, **run),
          DA._combine_fwd_xla(ops["o"], *args), dtype)
    got = DA._combine_bwd_call(ops["o"], ops["g"], *args, **run)
    want = DA._combine_bwd_xla(ops["o"], ops["g"], *args)
    close(got[0], want[0], dtype)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("H, D", [(4, 64), (6, 128)])
def test_the_kernel_path_differentiates_as_the_jnp_path(interpreted_kernels,
                                                        H, D):
    """Both functions through their ``custom_vjp`` on the kernels, the way
    a TPU runs them: value and every cotangent."""
    ops = operands(H, D, jnp.bfloat16, seed=3)
    kernel = counter("diff_attention.path", path="kernel")
    before = kernel.value
    got, pull = jax.vjp(lambda q: DA.lay_in_halves(q, D), ops["q"])
    same_values(got, DA._lay_fwd_xla(ops["q"], D))
    same_values(pull(ops["g_wide"])[0], DA._lay_bwd_xla(ops["g_wide"], D))
    args = ops["o"], ops["lam"], ops["scale"]
    got, pull = jax.vjp(lambda *a: DA.diff_combine(*a, EPS), *args)
    close(got, DA._combine_fwd_xla(*args, EPS), jnp.bfloat16)
    want = DA._combine_bwd_xla(ops["o"], ops["g"], *args[1:], EPS)
    for a, b in zip(pull(ops["g"]), want):
        close(a, b.astype(a.dtype), jnp.bfloat16)
    assert kernel.value == before + 2


def test_the_path_is_counted_once_a_call(monkeypatch):
    xla = counter("diff_attention.path", path="xla")
    kernel = counter("diff_attention.path", path="kernel")
    ops = operands(4, 64, jnp.float32)
    before = xla.value, kernel.value
    jax.grad(lambda q: DA.lay_in_halves(q, 64).sum())(ops["q"])
    assert (xla.value, kernel.value) == (before[0] + 1, before[1])
    jax.grad(lambda o: DA.diff_combine(o, ops["lam"], ops["scale"],
                                       EPS).sum())(ops["o"])
    assert (xla.value, kernel.value) == (before[0] + 2, before[1])
    # The kernels are for whole 128-lane heads, on a TPU (the flash
    # kernels' switch, which a compile for a described chip turns).
    assert not DA._runs_kernels(128)
    monkeypatch.setattr(DA._flash, "_interpret", lambda: False)
    assert DA._runs_kernels(128) and DA._runs_kernels(256)
    assert not DA._runs_kernels(16) and not DA._runs_kernels(192)


@pytest.mark.parametrize("call, columns", [
    (lambda x: DA.lay_in_halves(x, 64), 64 * 3),
    (lambda x: DA.diff_combine(x, jnp.float32(0.5), jnp.ones((128,)), EPS),
     128 * 3)])
def test_an_odd_number_of_heads_is_refused(call, columns):
    with pytest.raises(ValueError, match="no even number"):
        call(jnp.zeros((1, 8, columns)))
