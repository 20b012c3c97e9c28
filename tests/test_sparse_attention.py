"""``hvd.sparse_attention``: the indexer's exact selection and attention
over the selected set, against the plain reference
(benchmarks/lib/reference_sparse_moe.py), in float32 through the Pallas
interpreter (docs/sparse_attention.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from benchmarks.lib import reference_sparse_moe as ref
from benchmarks.lib.reference_gpt2 import _mm
from horovod_tpu.monitor.registry import counter
from horovod_tpu.ops import sparse_attention as sa

MM = _mm("float32")
T, H, HK, D, HI, DI, TOPK = 256, 4, 2, 16, 2, 8, 16


def _inputs(seed, T=T):
    ks = jax.random.split(jax.random.key(seed), 6)
    n = jax.random.normal
    return dict(q=n(ks[0], (T, H, D)), k=n(ks[1], (T, HK, D)),
                v=n(ks[2], (T, HK, D)), qi=n(ks[3], (T, HI, DI)),
                ki=n(ks[4], (T, DI)), w=n(ks[5], (T, HI)))


def _reference_selection(x, topk=TOPK, q_block=64):
    T = x["ki"].shape[0]
    tau = ref.selection(x["qi"], x["ki"], x["w"], topk, MM, q_block)
    scores = ref.index_scores(x["qi"], x["ki"], x["w"], MM)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    return tau, (scores >= tau[:, None]) & causal


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_selected_set_is_the_references(seed):
    """(b): in float32 the kernel's threshold and set are the reference's,
    rows with fewer than topk causal keys select them all, and a row keeps
    at least topk keys (ties at the threshold are kept)."""
    x = _inputs(seed)
    tau_ref, sel_ref = _reference_selection(x)
    mask, tau = hvd.index_select(x["qi"][None], x["ki"][None], x["w"][None],
                                 topk=TOPK)
    assert mask.dtype == jnp.int8 and mask.shape == (1, T, T)
    np.testing.assert_array_equal(np.asarray(mask[0] != 0),
                                  np.asarray(sel_ref))
    assert np.all(np.isinf(np.asarray(tau[0, :TOPK - 1])))
    np.testing.assert_allclose(np.asarray(tau[0, TOPK - 1:]),
                               np.asarray(tau_ref[TOPK - 1:]), rtol=1e-6)
    per_row = np.asarray(mask[0]).sum(1)
    np.testing.assert_array_equal(per_row[:TOPK], np.arange(1, TOPK + 1))
    assert np.all(per_row[TOPK:] >= TOPK)
    assert not np.asarray(jnp.triu(mask[0], 1)).any()


def test_ties_at_the_threshold_are_kept():
    """Scores that tie (here all zero: relu of a non-positive dot) are all
    selected, the reference's stated departure from a strict top-k."""
    qi = -jnp.ones((1, 128, 1, 8))
    ki = jnp.ones((1, 128, 8))
    mask, tau = hvd.index_select(qi, ki, jnp.ones((1, 128, 1)), topk=4)
    np.testing.assert_array_equal(np.asarray(mask[0]),
                                  np.tril(np.ones((128, 128), np.int8)))
    assert np.all(np.asarray(tau[0, 3:]) == 0.0)


def test_the_kernel_selects_alike_under_shard_map():
    """Inside ``shard_map`` (two sequences, one a device) the interpreter
    runs the same kernel body: each device's mask and threshold are what
    the kernel gives that sequence alone."""
    from jax.sharding import Mesh, PartitionSpec as P

    xs = [_inputs(3), _inputs(4)]
    args = tuple(jnp.stack([x[n] for x in xs]) for n in ("qi", "ki", "w"))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("d",))
    mask, tau = jax.jit(jax.shard_map(
        lambda *a: hvd.index_select(*a, topk=TOPK), mesh=mesh,
        in_specs=(P("d"),) * 3, out_specs=(P("d"),) * 2))(*args)
    for b, x in enumerate(xs):
        _, sel_ref = _reference_selection(x)
        np.testing.assert_array_equal(np.asarray(mask[b] != 0),
                                      np.asarray(sel_ref))
        alone = hvd.index_select(*(a[b:b + 1] for a in args), topk=TOPK)
        np.testing.assert_array_equal(np.asarray(tau[b]),
                                      np.asarray(alone[1][0]))


@pytest.mark.parametrize("seed", [0, 1])
def test_attention_over_the_references_set(seed):
    """(b): handed the reference's set, forward and the three gradients
    agree to float32 rounding (1e-5 of the largest entry: sums of up to 256
    products in another order)."""
    x = _inputs(seed)
    tau, sel = _reference_selection(x)
    ct = jax.random.normal(jax.random.key(9), (T, H, D))

    def want(q, k, v):
        return ref.attention(q, k, v, x["qi"], x["ki"], x["w"], tau, MM, 64,
                             selected=sel)

    def got(q, k, v):
        return hvd.masked_attention(q[None], k[None], v[None],
                                    sel[None].astype(jnp.int8))[0]

    args = (x["q"], x["k"], x["v"])
    np.testing.assert_allclose(np.asarray(got(*args)),
                               np.asarray(want(*args)), atol=2e-6)
    g_want = jax.grad(lambda *a: (want(*a) * ct).sum(), argnums=(0, 1, 2))(
        *args)
    g_got = jax.grad(lambda *a: (got(*a) * ct).sum(), argnums=(0, 1, 2))(
        *args)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_many_blocks_agree_with_one(monkeypatch):
    """A 4 x 4 grid of (128, 128) cells (causal cells skipped, their index
    maps clamped, statistics carried across key blocks, index keys walked
    in four chunks) gives what one whole-sequence cell gives."""
    x = _inputs(11, 512)
    args = tuple(x[n][None] for n in ("q", "k", "v", "qi", "ki", "w"))
    ct = jax.random.normal(jax.random.key(12), (1, 512, H, D))

    def run():
        mask, tau = hvd.index_select(*args[3:], topk=TOPK)
        o, grads = jax.value_and_grad(
            lambda q, k, v: (hvd.masked_attention(q, k, v, mask) * ct).sum(),
            argnums=(0, 1, 2))(*args[:3])
        return mask, tau, o, grads

    one = run()
    for name in ("_BLOCK_Q", "_BLOCK_K", "_INDEX_BLOCK_Q", "_INDEX_CHUNK"):
        monkeypatch.setattr(sa, name, 128)
    many = run()
    np.testing.assert_array_equal(np.asarray(one[0]), np.asarray(many[0]))
    np.testing.assert_array_equal(np.asarray(one[1]), np.asarray(many[1]))
    np.testing.assert_allclose(float(one[2]), float(many[2]), rtol=1e-5)
    for a, b in zip(one[3], many[3]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5 * float(jnp.abs(a).max()))


def test_sparse_attention_is_selection_then_attention():
    x = _inputs(4)
    _, sel = _reference_selection(x)
    o = hvd.sparse_attention(x["q"][None], x["k"][None], x["v"][None],
                             x["qi"][None], x["ki"][None], x["w"][None],
                             topk=TOPK)
    o2 = hvd.masked_attention(x["q"][None], x["k"][None], x["v"][None],
                              sel[None].astype(jnp.int8))
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o2))


def _selected(seed, T_):
    x = _inputs(seed, T_)
    return hvd.index_select(x["qi"][None], x["ki"][None], x["w"][None],
                            topk=TOPK)[0]


def _random_mask(low, high, shape):
    return jnp.asarray(np.random.default_rng(0).integers(
        low, high, shape).astype(np.int8))


# name -> () -> mask [B, T, T]: what the backward pass must read back
_SELECTIONS = {
    # every causal pair ties at the threshold: a full lower triangle
    "ties": lambda: jnp.asarray(np.tril(np.ones((1, 128, 128), np.int8))),
    "empty_upper_triangle": lambda: _selected(3, T),
    "T_not_a_multiple_of_1024": lambda: _selected(4, 384),
    # each of the eight row slabs has its own bit: no two rows alike
    "two_sequences_of_random_bits": lambda: _random_mask(0, 2, (2, 256, 256)),
    # masked_attention reads non-zero as "attend"
    "any_non_zero_is_selected": lambda: _random_mask(-2, 3, (1, 128, 128)),
}


@pytest.mark.parametrize("case", sorted(_SELECTIONS))
def test_packed_selection_unpacks_to_the_mask(case):
    """One bit a pair: ``[B, T/8, T]`` uint8, bit r of byte [t, s] is row
    ``r * T/8 + t``; unpacking gives the 0/1 int8 mask back to the bit."""
    mask = _SELECTIONS[case]()
    B, T_, _ = mask.shape
    packed = sa.pack_selection(mask)
    assert packed.dtype == jnp.uint8 and packed.shape == (B, T_ // 8, T_)
    want = np.asarray(mask != 0)
    rows = T_ // 8
    for r in (0, 3, 7):
        np.testing.assert_array_equal(np.asarray(packed >> r) & 1,
                                      want[:, r * rows:(r + 1) * rows])
    back = sa.unpack_selection(packed)
    assert back.dtype == jnp.int8 and back.shape == mask.shape
    np.testing.assert_array_equal(np.asarray(back), want.astype(np.int8))


@pytest.mark.parametrize("T_", [T, 384])
def test_the_backward_reads_the_selection_the_forward_made(monkeypatch, T_):
    """``sparse_attention``'s value and its gradients in q, k and v are, to
    the bit, ``masked_attention``'s on ``index_select``'s mask, and what
    the backward kernels give when handed that int8 mask itself (packing
    taken out: the arithmetic of before)."""
    x = _inputs(13, T_)
    names = ("q", "k", "v", "qi", "ki", "w")
    args = tuple(x[n][None] for n in names)
    ct = jax.random.normal(jax.random.key(14), (1, T_, H, D))
    mask, _ = hvd.index_select(*args[3:], topk=TOPK)

    def sparse(q, k, v):
        return (hvd.sparse_attention(q, k, v, *args[3:], topk=TOPK)
                * ct).sum()

    def masked(q, k, v):
        return (hvd.masked_attention(q, k, v, mask) * ct).sum()

    got = jax.value_and_grad(sparse, argnums=(0, 1, 2))(*args[:3])
    want = jax.value_and_grad(masked, argnums=(0, 1, 2))(*args[:3])
    monkeypatch.setattr(sa, "pack_selection", lambda m: m)
    monkeypatch.setattr(sa, "unpack_selection", lambda p: p)
    unpacked = jax.value_and_grad(masked, argnums=(0, 1, 2))(*args[:3])
    for other in (want, unpacked):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(other)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_no_gradient_reaches_the_indexer_or_passes_the_selection():
    """(d): the indexer's operands get exactly zero, and q, k, v get what
    attention over the FIXED set gives them."""
    x = _inputs(5)
    _, sel = _reference_selection(x)
    names = ("q", "k", "v", "qi", "ki", "w")

    def loss(*a):
        return hvd.sparse_attention(*(t[None] for t in a),
                                    topk=TOPK).sum()

    g = dict(zip(names, jax.grad(loss, argnums=range(6))(
        *(x[n] for n in names))))
    for n in ("qi", "ki", "w"):
        assert not np.asarray(g[n]).any(), n
    fixed = jax.grad(lambda q, k, v: hvd.masked_attention(
        q[None], k[None], v[None], sel[None].astype(jnp.int8)).sum(),
        argnums=(0, 1, 2))(x["q"], x["k"], x["v"])
    for n, b in zip("qkv", fixed):
        np.testing.assert_array_equal(np.asarray(g[n]), np.asarray(b))


def test_batch_and_bfloat16():
    """Two sequences at once select and attend as each alone does; bf16
    operands give the float32 result to bf16 rounding."""
    xs = [_inputs(6, 128), _inputs(7, 128)]
    both = {n: jnp.stack([x[n] for x in xs]) for n in xs[0]}
    o = hvd.sparse_attention(both["q"], both["k"], both["v"], both["qi"],
                             both["ki"], both["w"], topk=TOPK)
    for b, x in enumerate(xs):
        alone = hvd.sparse_attention(*(x[n][None] for n in (
            "q", "k", "v", "qi", "ki", "w")), topk=TOPK)
        np.testing.assert_array_equal(np.asarray(o[b]), np.asarray(alone[0]))
    _, sel = _reference_selection(xs[0])
    lo = hvd.masked_attention(
        *(xs[0][n][None].astype(jnp.bfloat16) for n in "qkv"),
        sel[None].astype(jnp.int8))
    hi = hvd.masked_attention(*(xs[0][n][None] for n in "qkv"),
                              sel[None].astype(jnp.int8))
    np.testing.assert_allclose(np.asarray(lo, np.float32), np.asarray(hi),
                               atol=0.05)


def test_trace_time_counters():
    """``sparse_attn.pairs_required`` / ``pairs_computed`` by kernel and
    ``sparse_attn.topk`` count at trace time what the call needs and what
    its tiles compute (one (128, 128) tile here: every pair)."""
    x = _inputs(8, 128)
    names = [("topk", "index"), ("pairs_required", "index"),
             ("pairs_computed", "index")] + [
        (n, k) for k in ("fwd", "bwd_dq", "bwd_dkv")
        for n in ("pairs_required", "pairs_computed")]

    def read():
        return {nk: counter(f"sparse_attn.{nk[0]}", kernel=nk[1]).value
                for nk in names}

    before = read()
    jax.grad(lambda q: hvd.sparse_attention(
        q[None], x["k"][None], x["v"][None], x["qi"][None], x["ki"][None],
        x["w"][None], topk=TOPK).sum())(x["q"])
    got = {nk: read()[nk] - before[nk] for nk in names}
    required = sum(min(t + 1, TOPK) for t in range(128))
    assert sa.pairs_required(128, TOPK) == required
    assert got[("topk", "index")] == TOPK
    assert got[("pairs_required", "index")] == required
    assert got[("pairs_computed", "index")] == 128 * 128
    for kern in ("fwd", "bwd_dq", "bwd_dkv"):
        assert got[("pairs_required", kern)] == H * required
        assert got[("pairs_computed", kern)] == H * 128 * 128


@pytest.mark.parametrize("B,T_", [(1, 128), (2, 256)])
def test_selection_bytes_counter(B, T_):
    """``sparse_attn.selection_bytes``: a differentiated call names
    ``B * T * T / 8`` bytes of packed selection; a call that is not
    differentiated packs nothing."""
    xs = [_inputs(20 + b, T_) for b in range(B)]
    args = tuple(jnp.stack([x[n] for x in xs])
                 for n in ("q", "k", "v", "qi", "ki", "w"))
    named = counter("sparse_attn.selection_bytes")
    before = named.value
    hvd.sparse_attention(*args, topk=TOPK)
    assert named.value == before
    jax.grad(lambda q: hvd.sparse_attention(q, *args[1:],
                                            topk=TOPK).sum())(args[0])
    assert named.value - before == B * T_ * T_ // 8


def test_shapes_that_cannot_be_blocked_are_refused():
    x = _inputs(0, 128)
    with pytest.raises(ValueError, match="shapes"):
        hvd.masked_attention(x["q"][None], x["k"][None], x["v"][None],
                             jnp.ones((1, 128, 64), jnp.int8))
    with pytest.raises(ValueError, match="shapes"):
        hvd.masked_attention(x["q"][None, :, :3], x["k"][None],
                             x["v"][None], jnp.ones((1, 128, 128), jnp.int8))
