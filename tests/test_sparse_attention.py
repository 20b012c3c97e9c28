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
    for name in ("_BLOCK_Q", "_BLOCK_K", "_BWD_BLOCK_Q", "_BWD_BLOCK_K",
                 "_INDEX_BLOCK_Q", "_INDEX_CHUNK"):
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


def _dense_grads(q, k, v, sel, ct):
    """Gradients of the plain float32 reference's attention over ``sel``
    [T, T] in q [T, H, D], k, v [T, Hk, D], cotangent ``ct``."""
    T_ = q.shape[0]
    none = jnp.zeros((T_, 1))

    def loss(q, k, v):
        return (ref.attention(q, k, v, none, none, none, none[:, 0], MM, 64,
                              selected=sel) * ct).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _one_key_rows(T_):
    """A selection whose rows of the second half keep ONE key each (an
    early one: whole tiles beside it hold nothing of the row), the first
    half a full lower triangle."""
    m = np.tril(np.ones((T_, T_), np.int8))
    m[T_ // 2:] = 0
    m[np.arange(T_ // 2, T_), np.arange(T_ // 2, T_) % 7] = 1
    return jnp.asarray(m)[None]


# name -> T -> mask [1, T, T]
_BACKWARD_MASKS = {
    "ties": lambda T_: jnp.asarray(np.tril(np.ones((1, T_, T_), np.int8))),
    "empty_upper_triangle": lambda T_: _selected(3, T_),
    "a_row_with_one_key": _one_key_rows,
}


@pytest.mark.parametrize("mask_name", sorted(_BACKWARD_MASKS))
@pytest.mark.parametrize("T_,G,bq,bk", [
    (256, 1, 128, 128), (256, 8, 128, 128), (256, 8, 128, 256),
    (384, 1, 128, 128), (384, 8, 128, 128), (256, 8, 256, 128)])
def test_fused_backward_is_the_two_kernels_to_the_bit(monkeypatch, mask_name,
                                                      T_, G, bq, bk):
    """One ``hvd_sparse_attn_bwd`` call gives the dq, dk and dv of
    ``hvd_sparse_attn_bwd_dq`` + ``_dkv`` BIT FOR BIT (a key block's
    contributions arrive in the same order: query blocks ascending, heads
    inside; a query block's likewise), and the dense float32 reference's
    gradients to float32 rounding; G = 1 and G = 8 query heads a KV head,
    square and oblong tiles, a grid of 2 x 2, 3 x 3, 2 x 1 and 1 x 2."""
    hk = 2
    ks = jax.random.split(jax.random.key(T_ + G), 4)
    q = jax.random.normal(ks[0], (T_, hk * G, D))
    k, v = (jax.random.normal(key, (T_, hk, D)) for key in ks[1:3])
    ct = jax.random.normal(ks[3], (T_, hk * G, D))
    mask = _BACKWARD_MASKS[mask_name](T_)
    for name in ("_BLOCK_Q", "_BWD_BLOCK_Q"):   # the same tiles on both
        monkeypatch.setattr(sa, name, bq)       # paths: the same order
    for name in ("_BLOCK_K", "_BWD_BLOCK_K"):   # of every sum
        monkeypatch.setattr(sa, name, bk)

    def grads():
        return jax.grad(lambda q, k, v: (hvd.masked_attention(
            q[None], k[None], v[None], mask)[0] * ct).sum(),
            argnums=(0, 1, 2))(q, k, v)

    fused = grads()
    monkeypatch.setattr(sa, "_FUSED_BWD_BUDGET", 0)
    split = grads()
    for a, b, c in zip(fused, split, _dense_grads(q, k, v, mask[0] != 0,
                                                  ct)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-5 * float(jnp.abs(c).max()))


def test_the_fused_backwards_own_blocks():
    """As shipped the fused backward walks (512, 1024) tiles (a 2 x 1 grid
    at T = 1024) where the forward and the two kernels walk (1024, 1024):
    its gradients are the dense reference's, and the two kernels' to
    float32 rounding (a key block's sum is cut in two)."""
    assert (sa._BWD_BLOCK_Q, sa._BWD_BLOCK_K) == (512, 1024)
    assert (sa._BLOCK_Q, sa._BLOCK_K) == (1024, 1024)
    T_ = 1024
    ks = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(ks[0], (T_, 2, D))
    k, v = (jax.random.normal(key, (T_, 1, D)) for key in ks[1:3])
    ct = jax.random.normal(ks[3], (T_, 2, D))
    mask = _random_mask(0, 2, (1, T_, T_)) | jnp.eye(T_, dtype=jnp.int8)
    mask = jnp.tril(mask)
    fused = jax.grad(lambda q, k, v: (hvd.masked_attention(
        q[None], k[None], v[None], mask)[0] * ct).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(fused, _dense_grads(q, k, v, mask[0] != 0, ct)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-5 * float(jnp.abs(c).max()))


def _backward_kernels(T_, D_):
    """The names of the ``pallas_call``s in the gradient's jaxpr at
    ``[1, T, 2 / 1 heads, D]``; nothing runs."""
    found = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.add(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    q, kv = (jax.ShapeDtypeStruct((1, T_, h, D_), jnp.bfloat16)
             for h in (2, 1))
    mask = jax.ShapeDtypeStruct((1, T_, T_), jnp.int8)
    walk(jax.make_jaxpr(jax.grad(lambda q, k, v, m: hvd.masked_attention(
        q, k, v, m).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(
            q, kv, kv, mask).jaxpr)
    return found - {"hvd_sparse_attn_fwd"}


@pytest.mark.parametrize("T_,D_,fused", [
    (16384, 128, True),    # the benchmark's: 16 MB of dk / dv a KV head
    (24576, 128, True),    # the budget to the byte (24 MB of the 96)
    (32768, 128, False),   # 32 MB: the two kernels
    (32768, 64, True),     # half the head, twice the sequence
    (65536, 64, False),
])
def test_the_backward_path_goes_by_the_shape(T_, D_, fused):
    """``8 * T * D`` bytes of float32 dk and dv a KV head against a quarter
    of the kernels' VMEM limit decide the path: no option, no name."""
    assert sa._FUSED_BWD_BUDGET == sa._VMEM_LIMIT // 4
    assert sa._fused_bwd_fits(T_, D_) == fused
    paths = {p: counter("sparse_attn.bwd_path", path=p)
             for p in ("fused", "split")}
    before = {p: c.value for p, c in paths.items()}
    assert _backward_kernels(T_, D_) == (
        {"hvd_sparse_attn_bwd"} if fused else
        {"hvd_sparse_attn_bwd_dq", "hvd_sparse_attn_bwd_dkv"})
    assert {p: c.value - before[p] for p, c in paths.items()} == {
        "fused": int(fused), "split": int(not fused)}


@pytest.mark.parametrize("path,bwd_kernels", [
    ("fused", ("bwd",)), ("split", ("bwd_dq", "bwd_dkv"))])
def test_trace_time_counters(monkeypatch, path, bwd_kernels):
    """``sparse_attn.pairs_required`` / ``pairs_computed`` by kernel and
    ``sparse_attn.topk`` count at trace time what the call needs and what
    its tiles compute (one (128, 128) tile here: every pair). The fused
    backward runs a tile once (``kernel="bwd"``): half of what the two
    kernels of the other path count between them; ``sparse_attn.bwd_path``
    says once a differentiated call which of the two it took."""
    if path == "split":
        monkeypatch.setattr(sa, "_FUSED_BWD_BUDGET", 0)
    x = _inputs(8, 128)
    names = [("topk", "index"), ("pairs_required", "index"),
             ("pairs_computed", "index")] + [
        (n, k) for k in ("fwd", "bwd", "bwd_dq", "bwd_dkv")
        for n in ("pairs_required", "pairs_computed")]

    def read():
        got = {nk: counter(f"sparse_attn.{nk[0]}", kernel=nk[1]).value
               for nk in names}
        got.update({p: counter("sparse_attn.bwd_path", path=p).value
                    for p in ("fused", "split")})
        return got

    before = read()
    jax.grad(lambda q: hvd.sparse_attention(
        q[None], x["k"][None], x["v"][None], x["qi"][None], x["ki"][None],
        x["w"][None], topk=TOPK).sum())(x["q"])
    got = {nk: v - before[nk] for nk, v in read().items()}
    required = sum(min(t + 1, TOPK) for t in range(128))
    assert sa.pairs_required(128, TOPK) == required
    assert got[("topk", "index")] == TOPK
    assert got[("pairs_required", "index")] == required
    assert got[("pairs_computed", "index")] == 128 * 128
    for kern in ("fwd", "bwd", "bwd_dq", "bwd_dkv"):
        ran = kern == "fwd" or kern in bwd_kernels
        assert got[("pairs_required", kern)] == ran * H * required
        assert got[("pairs_computed", kern)] == ran * H * 128 * 128
    assert {p: got[p] for p in ("fused", "split")} == {
        "fused": path == "fused", "split": path == "split"}


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
