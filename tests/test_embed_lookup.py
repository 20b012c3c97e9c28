"""``ops/embed_lookup.py``: the lookup's value against
``table.astype(dtype)[tokens]`` bit for bit, and its backward against the
float32 scatter-add of the same rows, the kernel in the Pallas interpreter
over blocks the vocabulary and the tokens do not fill; under
``hvd.shard_map``; the form told by the width alone and counted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.monitor.registry import counter
from horovod_tpu.ops import embed_lookup as EL

V = 300     # two table blocks of 128 rows and 44 rows of a third


def ids_of(kind: str, n: int, rs) -> np.ndarray:
    if kind == "distinct":
        return rs.permutation(V)[:n]
    if kind == "equal":
        return np.full(n, 131)
    if kind == "noised_and_clean":
        # sdar-30b-a3b's rows: a clean copy after a copy with a third of
        # its ids replaced by the mask's, so every clean id is there twice
        clean = rs.randint(0, V - 1, n // 2)
        noised = np.where(rs.rand(n // 2) < 0.3, V - 1, clean)
        return np.concatenate([noised, clean, clean[:n % 2]])
    if kind == "out_of_range":
        ids = rs.randint(0, V, n)
        ids[:6] = [V, V + 500, -1, -V, -V - 1, 2 ** 31 - 1]
        return ids
    assert kind == "uniform"
    return rs.randint(0, V, n)


def reference_grad(ids, dx):
    """What the parent's transpose adds, in float32."""
    return jnp.zeros((V, dx.shape[1]), jnp.float32).at[ids].add(
        dx.astype(jnp.float32))


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks a few hundred rows fill several times over."""
    monkeypatch.setattr(EL, "BLOCK_ROWS", 128)
    monkeypatch.setattr(EL, "CHUNK_ROWS", 64)


CASES = [
    # ids, N, width, dx's type
    ("distinct", 256, 768, jnp.bfloat16),
    ("distinct", 192, 2560, jnp.float32),
    ("equal", 256, 2048, jnp.bfloat16),        # one run over every chunk
    ("equal", 70, 768, jnp.float32),
    ("noised_and_clean", 256, 2560, jnp.bfloat16),
    ("noised_and_clean", 256, 768, jnp.float32),
    ("uniform", 201, 2560, jnp.bfloat16),      # N fills no whole chunk
    ("uniform", 37, 2048, jnp.float32),        # less than one chunk
    ("uniform", 640, 768, jnp.bfloat16),       # two rows an id and more
    ("out_of_range", 128, 2048, jnp.bfloat16),
    ("out_of_range", 100, 768, jnp.float32),
    ("uniform", 96, 192, jnp.bfloat16),        # no whole lane tiles: the
    ("equal", 96, 72, jnp.float32),            # compiler's scatter
]


@pytest.mark.parametrize("kind,N,C,dtype", CASES)
def test_value_and_gradient(small_blocks, kind, N, C, dtype):
    rs = np.random.RandomState(N + C)
    table = jnp.asarray(rs.randn(V, C), jnp.float32)
    ids = jnp.asarray(ids_of(kind, N, rs), jnp.int32)
    if N % 2 == 0:
        ids = ids.reshape(2, -1)        # a batch, as the models hand it
    dx = jnp.asarray(rs.randn(*ids.shape, C), dtype)

    got, vjp = jax.vjp(lambda t: EL.embed_lookup(t, ids, dtype), table)
    want = table.astype(dtype)[ids]
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))

    form = "sorted_rows_kernel" if C % 128 == 0 else "scatter"
    before = counter("embed.grad_rows", form=form).value
    grad, = vjp(dx)
    assert counter("embed.grad_rows", form=form).value - before == N
    # the parent's own transpose, summed in float32: an id out of range
    # reads the clamped row and adds to none
    want_grad, = jax.vjp(lambda t: t[ids], table)[1](dx.astype(jnp.float32))
    assert grad.dtype == jnp.float32 and grad.shape == table.shape
    np.testing.assert_allclose(
        np.asarray(grad), np.asarray(want_grad), rtol=0,
        atol=1e-6 * max(1.0, float(jnp.abs(want_grad).max())))
    if kind != "out_of_range":
        np.testing.assert_array_equal(
            np.asarray(want_grad),
            np.asarray(reference_grad(ids.reshape(-1), dx.reshape(-1, C))))


@pytest.mark.parametrize("C,dtype", [(256, jnp.bfloat16), (256, jnp.float32),
                                     (96, jnp.bfloat16)])
def test_under_shard_map(small_blocks, C, dtype):
    """A replicated table and tokens split over the mesh, as the training
    step has them: the table's cotangent is the ranks' sum. (Off the TPU
    the rows take the scatter here whatever their width;
    ``tests/test_tpu_lowering.py`` holds the kernel under the map.)"""
    rs = np.random.RandomState(C)
    n = hvd.size()
    table = jnp.asarray(rs.randn(V, C), jnp.float32)
    ids = jnp.asarray(rs.randint(0, V, (n, 80)), jnp.int32)
    w = jnp.asarray(rs.randn(n, 80, C), dtype)

    def loss(lookup):
        def f(table, ids, w):
            def local(t):
                return (lookup(t, ids).astype(jnp.float32)
                        * w.astype(jnp.float32)).sum()
            value, grad = jax.value_and_grad(local)(table)
            return hvd.allreduce(value, op=hvd.Sum), grad
        return jax.jit(hvd.shard_map(
            f, mesh=hvd.mesh(),
            in_specs=(P(), P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(), P())))(table, ids, w)

    value, grad = loss(lambda t, i: EL.embed_lookup(t, i, dtype))
    want_value, want_grad = loss(lambda t, i: t.astype(dtype)[i])
    np.testing.assert_allclose(float(value), float(want_value), rtol=1e-5)
    # the parent's adds its rows in ``dtype``: held to float32's sum
    np.testing.assert_allclose(
        np.asarray(grad), np.asarray(reference_grad(
            ids.reshape(-1), w.reshape(-1, C))), rtol=0, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(grad), np.asarray(want_grad), rtol=0,
        atol=2e-5 if dtype == jnp.float32 else 0.1)


def test_every_plan_is_blocks_plus_chunks_long_whatever_the_ids():
    """The grid is fixed by the shapes: each block is written once, in
    turn, and a chunk is read by every block whose ids it holds."""
    rs = np.random.RandomState(0)
    blocks, rows, chunks, chunk = 5, 16, 4, 8
    for ids in (rs.randint(0, 70, 32), np.full(32, 17), np.arange(32) * 2,
                np.full(32, 80)):
        ids = np.sort(np.where(ids >= 70, blocks * rows, ids))
        block, chunk_of, kind = (np.asarray(a) for a in EL._walk_plan(
            jnp.asarray(ids, jnp.int32), blocks, rows, chunks, chunk))
        assert len(block) == blocks + chunks
        live = kind != EL._SKIP
        assert (np.diff(block) >= 0).all() and (np.diff(block) <= 1).all()
        assert sorted(block[kind == EL._FIRST]) == list(range(blocks))
        got = {(b, c) for b, c in zip(block[live], chunk_of[live])}
        need = {(i // rows, at // chunk) for at, i in enumerate(ids)
                if i < blocks * rows}
        assert need <= got and len(got) == live.sum()
