"""ZeRO-1 sharded optimizer tests (docs/zero.md).

Core invariants:
  * the sharded update is numerically the replicated update — bit-identical
    for SGD given the same gradients, allclose for Adam across a training
    trajectory;
  * every optimizer-moment leaf is exactly ``1/world`` of its bucket's
    padded size (the memory claim);
  * composition with the quantized int8 wire + error feedback, local
    gradient accumulation, and gradient predivide;
  * host-side state reshard round-trips through ``hvd.elastic`` at a
    different world size.

All compiled tests run on the 8-device CPU mesh shaped 2x4 so the
reduce-scatter/all-gather decomposition has a real cross (DCN) hop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops import fusion

N = 8


@pytest.fixture(scope="module", autouse=True)
def _mesh_2x4():
    """Re-init the world as an emulated 2-host x 4-chip mesh so the
    reduce-scatter/all-gather decomposition (and the quantized DCN leg)
    has a real cross hop; restore the default mesh for later modules."""
    hvd.shutdown()
    hvd.init(mesh_shape=(2, 4))
    yield
    hvd.shutdown()
    hvd.init()


def loss_fn(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)


def make_data(rng, n=96, d=5):
    x = rng.randn(n, d).astype(np.float32)
    y = (x @ rng.randn(d, 1).astype(np.float32)
         + 0.1 * rng.randn(n, 1).astype(np.float32))
    return x, y


def init_params(d=5):
    return {"w": jnp.zeros((d, 1)), "b": jnp.zeros((1,))}


def _put_zero_state(state, mesh):
    spec = hvd.zero_state_pspecs(state)
    return jax.device_put(
        state, jax.tree.map(lambda s: NamedSharding(mesh, s), spec)), spec


def train(tx, zero, x, y, steps, bs=16, reduce_in_optimizer=True):
    """shard_map DP training; under ``reduce_in_optimizer`` the raw
    per-rank local gradients are handed to the optimizer (the canonical
    ZeRO step structure)."""
    params = init_params(x.shape[1])
    state = tx.init(params)
    mesh = hvd.mesh()
    if zero:
        state, sspec = _put_zero_state(state, mesh)
    else:
        sspec = jax.tree.map(lambda _: P(), state)

    @jax.jit
    def step(params, state, xb, yb):
        def spmd(params, state, xb, yb):
            loss, grads = hvd.value_and_grad(
                loss_fn, reduce=not reduce_in_optimizer)(params, (xb, yb))
            updates, ns = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), ns, \
                hvd.allreduce(loss)

        return hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), sspec, P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(), sspec, P()))(params, state, xb, yb)

    losses = []
    for i in range(steps):
        params, state, loss = step(params, state,
                                   jnp.asarray(x[i * bs:(i + 1) * bs]),
                                   jnp.asarray(y[i * bs:(i + 1) * bs]))
        losses.append(float(loss))
    return params, state, losses


# --- parity ----------------------------------------------------------------


def test_sgd_update_bit_identical_to_replicated():
    """Same gradients in, bit-identical updates out: both the sharded and
    the replicated SGD-momentum update run in ONE compiled step on the
    identical auto-psummed gradient, over 3 steps of evolving moments."""
    rng = np.random.RandomState(0)
    x, y = make_data(rng, n=48)
    tx_z = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9), zero=True)
    tx_r = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    params = init_params()
    sz = tx_z.init(params)
    sr = tx_r.init(params)
    mesh = hvd.mesh()
    sz, zspec = _put_zero_state(sz, mesh)
    rspec = jax.tree.map(lambda _: P(), sr)

    @jax.jit
    def step(params, sz, sr, xb, yb):
        def spmd(params, sz, sr, xb, yb):
            loss, grads = jax.value_and_grad(loss_fn)(params, (xb, yb))
            uz, nsz = tx_z.update(grads, sz, params)
            ur, nsr = tx_r.update(grads, sr, params)
            return uz, ur, nsz, nsr

        return hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), zspec, rspec, P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(), P(), zspec, rspec))(params, sz, sr, xb, yb)

    for i in range(3):
        uz, ur, sz, sr = step(params, sz, sr,
                              jnp.asarray(x[i * 16:(i + 1) * 16]),
                              jnp.asarray(y[i * 16:(i + 1) * 16]))
        for k in ur:
            np.testing.assert_array_equal(np.asarray(uz[k]),
                                          np.asarray(ur[k]))
        params = optax.apply_updates(params, ur)


def test_sgd_training_parity_local_grads():
    """Full training trajectory with the canonical ZeRO structure (local
    grads → optimizer-owned reduce-scatter) matches replicated training
    (auto-psummed grads) to fp tolerance."""
    rng = np.random.RandomState(1)
    x, y = make_data(rng)
    pz, _, _ = train(hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                              zero=True),
                     True, x, y, steps=4)
    pr, _, _ = train(hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9)),
                     False, x, y, steps=4)
    for k in pr:
        np.testing.assert_allclose(np.asarray(pz[k]), np.asarray(pr[k]),
                                   rtol=2e-5, atol=1e-7)


def test_adam_training_parity():
    rng = np.random.RandomState(2)
    x, y = make_data(rng)
    pz, _, _ = train(hvd.DistributedOptimizer(optax.adam(1e-2), zero=True),
                     True, x, y, steps=4)
    pr, _, _ = train(hvd.DistributedOptimizer(optax.adam(1e-2)),
                     False, x, y, steps=4)
    for k in pr:
        np.testing.assert_allclose(np.asarray(pz[k]), np.asarray(pr[k]),
                                   rtol=1e-5, atol=1e-6)


def test_zero_matches_single_device_global_batch():
    """The reference's core correctness property, ZeRO edition: sharded DP
    training == single-device training on the concatenated batch."""
    rng = np.random.RandomState(3)
    x, y = make_data(rng, n=64)
    pz, _, _ = train(hvd.DistributedOptimizer(optax.sgd(0.1), zero=True),
                     True, x, y, steps=4)
    params = init_params()
    opt = optax.sgd(0.1)
    state = opt.init(params)

    @jax.jit
    def step(params, state, xb, yb):
        grads = jax.grad(loss_fn)(params, (xb, yb))
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    for i in range(4):
        params, state = step(params, state,
                             jnp.asarray(x[i * 16:(i + 1) * 16]),
                             jnp.asarray(y[i * 16:(i + 1) * 16]))
    for k in params:
        np.testing.assert_allclose(np.asarray(pz[k]), np.asarray(params[k]),
                                   rtol=1e-4, atol=1e-6)


# --- state layout ----------------------------------------------------------


def test_moment_leaves_are_one_world_th():
    """Every non-scalar inner-state leaf is a flat bucket array whose
    per-rank shard is exactly padded_size // world — the ZeRO memory
    claim, asserted on the device shards themselves."""
    rng = np.random.RandomState(4)
    x, y = make_data(rng)
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), zero=True)
    _, state, _ = train(tx, True, x, y, steps=1)
    plan = fusion.plan_buckets(jax.tree.leaves(init_params()),
                               shard_multiple=N)
    padded = {b.padded_size for b in plan}
    moment_leaves = [l for l in jax.tree.leaves(state.inner)
                     if getattr(l, "ndim", 0) >= 1]
    assert moment_leaves, "no moment leaves found"
    for leaf in moment_leaves:
        assert leaf.shape[0] in padded  # global view: the full flat bucket
        # the actual per-device shard is 1/world of it
        shards = {s.data.shape for s in leaf.addressable_shards}
        assert shards == {(leaf.shape[0] // N,)}, shards


def test_zero_state_pspecs_shape():
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), zero=True)
    state = tx.init(init_params())
    spec = hvd.zero_state_pspecs(state)
    flat_state = jax.tree.leaves(state)
    flat_spec = jax.tree.leaves(spec, is_leaf=lambda s: isinstance(s, P))
    assert len(flat_state) == len(flat_spec)
    for l, s in zip(flat_state, flat_spec):
        if getattr(l, "ndim", 0) >= 1:
            assert s == P(hvd.HVD_AXES)
        else:
            assert s == P()


def test_plan_buckets_shard_multiple():
    leaves = [jnp.zeros((130,)), jnp.zeros((7,)), jnp.zeros((3, 3))]
    for world in (1, 3, 8):
        plan = fusion.plan_buckets(leaves, shard_multiple=world)
        for b in plan:
            assert b.padded_size % np.lcm(fusion.ATOMIC_UNIT, world) == 0
        # leaf->bucket assignment is world-independent
        base = fusion.plan_buckets(leaves)
        assert [b.leaf_indices for b in plan] == \
            [b.leaf_indices for b in base]
    # shard slicing round-trips
    buf = jnp.arange(192.0)
    shards = [fusion.shard_slice(buf, 8, r) for r in range(8)]
    assert all(s.shape == (24,) for s in shards)
    np.testing.assert_array_equal(np.asarray(fusion.shard_unslice(shards)),
                                  np.asarray(buf))


# --- primitives ------------------------------------------------------------


def test_reduce_scatter_all_gather_roundtrip():
    rng = np.random.RandomState(5)
    x = rng.randn(N, 256).astype(np.float32)

    def f(v):
        sh = hvd.reduce_scatter(v[0], op=hvd.Sum)
        return sh, hvd.all_gather(sh)

    sh, full = hvd.shard_map(
        f, mesh=hvd.mesh(), in_specs=P(hvd.HVD_AXES),
        out_specs=(P(hvd.HVD_AXES), P()))(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(full), x.sum(0), rtol=1e-5)
    # the scatter shards concatenate (rank-major) to the reduction
    np.testing.assert_allclose(np.asarray(sh).ravel(), x.sum(0), rtol=1e-5)


def test_reduce_scatter_average_divides():
    rng = np.random.RandomState(6)
    x = rng.randn(N, 64).astype(np.float32)

    def f(v):
        return hvd.all_gather(hvd.reduce_scatter(v[0], op=hvd.Average))

    out = hvd.shard_map(f, mesh=hvd.mesh(), in_specs=P(hvd.HVD_AXES),
                        out_specs=P())(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), x.mean(0), rtol=1e-5)


def test_reduce_scatter_rejects_indivisible():
    with pytest.raises(ValueError, match="does not divide"):
        hvd.shard_map(lambda v: hvd.reduce_scatter(v[0]),
                      mesh=hvd.mesh(), in_specs=P(hvd.HVD_AXES),
                      out_specs=P(hvd.HVD_AXES))(
            jnp.zeros((N, 12), jnp.float32))


def test_quantized_reduce_scatter_error_bounded():
    """int8 DCN leg: the per-element error of the quantized reduce-scatter
    is bounded by the sum of per-sender block scales / 254."""
    rng = np.random.RandomState(7)
    x = rng.randn(N, 512).astype(np.float32)

    def f(v):
        sh = hvd.reduce_scatter(v[0], op=hvd.Sum, quantized=True, block=64)
        return hvd.all_gather(sh)

    out = hvd.shard_map(f, mesh=hvd.mesh(), in_specs=P(hvd.HVD_AXES),
                        out_specs=P())(jnp.asarray(x))
    exact = x.sum(0)
    # Only the DCN (cross=2) hop quantizes; the 4-rank ICI leg is exact.
    # Each of the 2 cross senders quantizes its ICI-summed shard (absmax
    # up to 4x the input absmax), error <= scale/2 per element.
    scale_bound = 2 * (4 * np.abs(x).max() / 127.0)
    assert float(np.abs(np.asarray(out) - exact).max()) <= scale_bound


# --- composition -----------------------------------------------------------


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_quantized_error_feedback_compose(stage):
    """zero + quantized, every stage: training tracks the fp ZeRO run of
    the same stage and the EF residuals become (and stay) active — the
    reduce-scatter's at every stage, the all-gather's where the step
    ends in one (stage 3 gathers at the head of the next forward)."""
    rng = np.random.RandomState(8)
    x, y = make_data(rng)
    tq = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=stage,
                                  quantized=True)
    tf_ = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=stage,
                                   quantized=False)
    if stage == 3:
        pq, _, sq, lq = train3(tq, x, y, steps=6)
        pf, _, _, _ = train3(tf_, x, y, steps=6)
    else:
        pq, sq, lq = train(tq, True, x, y, steps=6)
        pf, _, _ = train(tf_, True, x, y, steps=6)
    assert lq[-1] < lq[0]  # trains
    for k in pf:
        np.testing.assert_allclose(np.asarray(pq[k]), np.asarray(pf[k]),
                                   rtol=0.05, atol=5e-3)
    assert isinstance(sq, hvd.ZeroState)
    rs = [l for l in jax.tree.leaves(sq.residual) if l is not None]
    assert rs and any(float(jnp.abs(l).max()) > 0 for l in rs)
    # residuals are shard-local: [world, padded/local] and [world, padded/world]
    plan = fusion.plan_buckets(jax.tree.leaves(init_params()),
                               shard_multiple=N)
    local = hvd.local_size()
    assert {tuple(l.shape) for l in rs} == \
        {(N, b.padded_size // local) for b in plan}
    if stage == 3:
        assert sq.gather_residual is None  # no trailing all-gather leg
        return
    ag = [l for l in jax.tree.leaves(sq.gather_residual) if l is not None]
    assert ag and any(float(jnp.abs(l).max()) > 0 for l in ag)
    assert {tuple(l.shape) for l in ag} == \
        {(N, b.padded_size // N) for b in plan}


def test_zero_backward_passes_accumulates_shard():
    """k accumulation microbatches then one apply == one step on the
    concatenated batch; the accumulator leaf is bucket-flat (1/world per
    rank), not a full gradient replica."""
    rng = np.random.RandomState(9)
    x, y = make_data(rng)
    tk = hvd.DistributedOptimizer(optax.sgd(0.1), zero=True,
                                  backward_passes_per_step=2)
    pk, sk, _ = train(tk, True, x, y, steps=2)
    t1 = hvd.DistributedOptimizer(optax.sgd(0.1), zero=True)
    p1, _, _ = train(t1, True, x, y, steps=1, bs=32)
    for k in p1:
        np.testing.assert_allclose(np.asarray(pk[k]), np.asarray(p1[k]),
                                   rtol=2e-5, atol=1e-7)
    plan = fusion.plan_buckets(jax.tree.leaves(init_params()),
                               shard_multiple=N)
    acc = jax.tree.leaves(sk.inner.acc_grads)
    assert {l.shape for l in acc} == {(b.padded_size,) for b in plan}
    for l in acc:  # sharded 1/world on device
        assert {s.data.shape for s in l.addressable_shards} == \
            {(l.shape[0] // N,)}


def test_zero_gradient_predivide():
    rng = np.random.RandomState(10)
    x, y = make_data(rng)
    pp, _, _ = train(hvd.DistributedOptimizer(
        optax.sgd(0.1), zero=True, gradient_predivide_factor=4.0),
        True, x, y, steps=2)
    pa, _, _ = train(hvd.DistributedOptimizer(optax.sgd(0.1), zero=True),
                     True, x, y, steps=2)
    for k in pa:
        np.testing.assert_allclose(np.asarray(pp[k]), np.asarray(pa[k]),
                                   rtol=1e-5, atol=1e-7)


def test_zero_env_knob(monkeypatch):
    from horovod_tpu.common import basics as B
    import dataclasses

    cfg = dataclasses.replace(B.config(), zero_sharding=True)
    monkeypatch.setattr(B._state, "config", cfg)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    state = tx.init(init_params())
    assert isinstance(state, hvd.ZeroState)


def test_eager_world_of_one_matches_plain_optimizer():
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), zero=True)
    ref = optax.adam(1e-2)
    params = init_params()
    rng = np.random.RandomState(11)
    x, y = make_data(rng, n=16)
    g = jax.grad(loss_fn)(params, (jnp.asarray(x), jnp.asarray(y)))
    uz, _ = tx.update(g, tx.init(params), params)
    ur, _ = ref.update(g, ref.init(params), params)
    for k in ur:
        np.testing.assert_allclose(np.asarray(uz[k]), np.asarray(ur[k]),
                                   rtol=1e-6, atol=1e-8)


# --- elastic reshard -------------------------------------------------------


def test_elastic_reshard_roundtrip():
    """ZeRO state round-trips through hvd.elastic save/restore at a
    different world size: 8 → 3 (different lcm padding: 64 vs 192) → 8 is
    the identity on every moment leaf, and training continues
    bit-identically afterwards."""
    rng = np.random.RandomState(12)
    x, y = make_data(rng)
    params0 = init_params()
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), zero=True)
    p1, s1, _ = train(tx, True, x, y, steps=2)
    host_state = jax.device_get(s1)

    # world 3 uses a different padding unit (lcm(64,3)=192)
    r3 = hvd.zero_reshard_state(host_state, params0, from_world=8,
                                to_world=3, to_local_size=3)
    plan3 = fusion.plan_buckets(jax.tree.leaves(params0), shard_multiple=3)
    for l in jax.tree.leaves(r3.inner):
        if getattr(l, "ndim", 0) >= 1:
            assert l.shape[0] in {b.padded_size for b in plan3}
            assert l.shape[0] % 3 == 0

    back = hvd.zero_reshard_state(r3, params0, from_world=3, to_world=8,
                                  to_local_size=4)
    for a, b in zip(jax.tree.leaves(host_state.inner),
                    jax.tree.leaves(back.inner)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # ride the hvd.elastic state container through save/restore/sync
    state_obj = hvd.elastic.JaxState(params=p1, opt_state=back)
    state_obj.save()
    state_obj.opt_state = jax.tree.map(jnp.zeros_like, back)  # "crash"
    state_obj.restore()
    restored = state_obj.opt_state
    for a, b in zip(jax.tree.leaves(host_state.inner),
                    jax.tree.leaves(restored.inner)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # continuing from the restored state == continuing uninterrupted
    mesh = hvd.mesh()
    sspec = hvd.zero_state_pspecs(restored)

    @jax.jit
    def step(params, state, xb, yb):
        def spmd(params, state, xb, yb):
            loss, grads = hvd.value_and_grad(
                loss_fn, reduce=False)(params, (xb, yb))
            updates, ns = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), ns

        return hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), sspec, P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(), sspec))(params, state, xb, yb)

    xb, yb = jnp.asarray(x[32:48]), jnp.asarray(y[32:48])
    restored_dev = jax.device_put(
        restored, jax.tree.map(lambda s: NamedSharding(mesh, s), sspec))
    p_resumed, _ = step(state_obj.params, restored_dev, xb, yb)
    p_straight, _ = step(p1, s1, xb, yb)
    for k in p_straight:
        np.testing.assert_array_equal(np.asarray(p_resumed[k]),
                                      np.asarray(p_straight[k]))


# --- ZeRO stages 1/2/3 (docs/zero.md) --------------------------------------


def _put(tree, spec, mesh=None):
    mesh = mesh or hvd.mesh()
    return jax.device_put(
        tree, jax.tree.map(lambda s: NamedSharding(mesh, s), spec))


def test_stage123_parity_one_program():
    """The stage-parity contract: all three stage updates run
    side-by-side in ONE compiled step sharing a single gradient
    computation (the bitwise methodology of
    test_sgd_update_bit_identical_to_replicated). Stage 1 vs 2 is
    bit-identical over the whole 3-step trajectory; stage 3 tracks at
    ≤1e-5 rel (XLA fuses the structurally different shard-apply path
    with different FMA formation — ulp-level compiler noise; gradients,
    moments, and shard updates are bit-identical, verified where the
    expressions coincide)."""
    rng = np.random.RandomState(20)
    x, y = make_data(rng)
    params0 = init_params()
    tpl = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                      params0)
    mesh = hvd.mesh()
    txs = [hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                    zero_stage=s) for s in (1, 2, 3)]
    states = [tx.init(params0) for tx in txs]
    sspecs = [hvd.zero_state_pspecs(s) for s in states]
    states = [_put(s, sp, mesh) for s, sp in zip(states, sspecs)]
    psh = hvd.zero3_shard_params(params0)
    pspec = hvd.zero3_param_pspecs(psh)
    psh = _put(psh, pspec, mesh)

    @jax.jit
    def step(p, psh, s1, s2, s3, xb, yb):
        def spmd(p, psh, s1, s2, s3, xb, yb):
            pg = hvd.zero3_gather_params(psh, tpl)
            _, g = hvd.value_and_grad(loss_fn, zero=True)(pg, (xb, yb))
            u1, ns1 = txs[0].update(g, s1, p)
            u2, ns2 = txs[1].update(g, s2, p)
            u3, ns3 = txs[2].update(g, s3, psh)
            return (optax.apply_updates(p, u1),
                    optax.apply_updates(p, u2),
                    optax.apply_updates(psh, u3), ns1, ns2, ns3)

        return hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), pspec, *sspecs, P(hvd.HVD_AXES),
                      P(hvd.HVD_AXES)),
            out_specs=(P(), P(), pspec, *sspecs))(
            p, psh, s1, s2, s3, xb, yb)

    p = params0
    for i in range(3):
        xb = jnp.asarray(x[i * 16:(i + 1) * 16])
        yb = jnp.asarray(y[i * 16:(i + 1) * 16])
        p1, p2, psh, *states = step(p, psh, *states, xb, yb)
        p3 = hvd.zero3_gather_params(jax.device_get(psh), params0)
        for k in p1:
            np.testing.assert_array_equal(np.asarray(p1[k]),
                                          np.asarray(p2[k]))
            np.testing.assert_allclose(np.asarray(p1[k]),
                                       np.asarray(p3[k]),
                                       rtol=1e-5, atol=1e-7)
        p = p1
    # the stage-3 state kept no gather residual and its inner moments
    # match stage 2's bit-for-bit (same reduce-scattered shards in)
    s2f, s3f = jax.device_get(states[1]), jax.device_get(states[2])
    assert s3f.gather_residual is None
    for a, b in zip(jax.tree.leaves(s2f.inner), jax.tree.leaves(s3f.inner)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_true_is_stage2_alias():
    """``zero=True`` (the PR-4 spelling) and ``zero_stage=2`` build the
    identical transformation: same state classes, bit-identical 3-step
    trajectory."""
    rng = np.random.RandomState(21)
    x, y = make_data(rng)
    pa, sa, _ = train(hvd.DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9), zero=True,
        backward_passes_per_step=2), True, x, y, steps=4, bs=8)
    pb, sb, _ = train(hvd.DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9), zero_stage=2,
        backward_passes_per_step=2), True, x, y, steps=4, bs=8)
    assert type(sa.inner) is type(sb.inner)
    for k in pa:
        np.testing.assert_array_equal(np.asarray(pa[k]), np.asarray(pb[k]))
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_stage_env_knob(monkeypatch):
    import dataclasses

    from horovod_tpu.common import basics as B

    cfg = dataclasses.replace(B.config(), zero_stage=1)
    monkeypatch.setattr(B._state, "config", cfg)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    state = tx.init(init_params())
    assert isinstance(state, hvd.ZeroState)
    # the boolean knob still maps to stage 2
    cfg = dataclasses.replace(B.config(), zero_stage=0, zero_sharding=True)
    monkeypatch.setattr(B._state, "config", cfg)
    state = hvd.DistributedOptimizer(optax.sgd(0.1)).init(init_params())
    assert isinstance(state, hvd.ZeroState)


def test_stage1_full_accumulator_layout():
    """Stage 1 + backward_passes_per_step: the gradient accumulator is
    the classic FULL per-rank local-gradient state ([world, *shape]
    leading-axis leaves — what stage 2 shrinks world×), k microbatches
    then one apply matches one big-batch step, and the stage-2
    trajectory agrees to fp tolerance."""
    rng = np.random.RandomState(22)
    x, y = make_data(rng)
    t1 = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=1,
                                  backward_passes_per_step=2)
    p1, s1, _ = train(t1, True, x, y, steps=2)
    assert isinstance(s1.inner, hvd.ZeroFullMultiStepsState)
    # full model-layout accumulator, per-rank leading axis
    for acc, leaf in zip(s1.inner.acc, jax.tree.leaves(init_params())):
        assert tuple(acc.shape) == (N,) + tuple(leaf.shape)
        # sharded over the leading axis: each device holds [1, *shape]
        assert {s.data.shape[0] for s in acc.addressable_shards} == {1}
        # cycle boundary after 2 steps of k=2: accumulator drained
        assert float(jnp.abs(acc).max()) == 0.0
    tb = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=1)
    pb, _, _ = train(tb, True, x, y, steps=1, bs=32)
    for k in pb:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(pb[k]),
                                   rtol=2e-5, atol=1e-7)
    t2 = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=2,
                                  backward_passes_per_step=2)
    p2, s2, _ = train(t2, True, x, y, steps=2)
    assert hasattr(s2.inner, "acc_grads")  # the 1/world shard accumulator
    for k in p2:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=2e-5, atol=1e-7)


def train3(tx, x, y, steps, bs=16, **gather_kw):
    """Stage-3 training loop: the loop owns flat bucket shards."""
    params0 = init_params(x.shape[1])
    tpl = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                      params0)
    mesh = hvd.mesh()
    psh = hvd.zero3_shard_params(params0)
    pspec = hvd.zero3_param_pspecs(psh)
    psh = _put(psh, pspec, mesh)
    state = tx.init(params0)
    sspec = hvd.zero_state_pspecs(state)
    state = _put(state, sspec, mesh)

    @jax.jit
    def step(psh, s, xb, yb):
        def spmd(psh, s, xb, yb):
            p = hvd.zero3_gather_params(psh, tpl, **gather_kw)
            loss, grads = hvd.value_and_grad(
                loss_fn, zero_stage=3)(p, (xb, yb))
            u, ns = tx.update(grads, s, psh)
            return optax.apply_updates(psh, u), ns, hvd.allreduce(loss)

        return hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(pspec, sspec, P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(pspec, sspec, P()))(psh, s, xb, yb)

    losses = []
    for i in range(steps):
        psh, state, loss = step(psh, state,
                                jnp.asarray(x[i * bs:(i + 1) * bs]),
                                jnp.asarray(y[i * bs:(i + 1) * bs]))
        losses.append(float(loss))
    params = hvd.zero3_gather_params(jax.device_get(psh), params0)
    return params, jax.device_get(psh), state, losses


def test_stage3_param_shard_shapes_and_training():
    """Stage 3: every persistent parameter buffer on device is exactly
    padded//world (the memory claim), the loop trains, and the result
    tracks the stage-2 run at fp tolerance."""
    rng = np.random.RandomState(23)
    x, y = make_data(rng)
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), zero_stage=3)
    p3, psh, state, losses = train3(tx, x, y, steps=6)
    assert losses[-1] < losses[0]
    plan = fusion.plan_buckets(jax.tree.leaves(init_params()),
                               shard_multiple=N)
    assert len(psh) == len(plan)
    # device shards: 1/world of the padded bucket — nothing bigger
    # persists (host view is the global [padded] bucket)
    dev = jax.device_put(psh, jax.tree.map(
        lambda _: NamedSharding(hvd.mesh(), P(hvd.HVD_AXES)), tuple(psh)))
    for buf, b in zip(dev, plan):
        assert buf.shape == (b.padded_size,)
        assert {s.data.shape for s in buf.addressable_shards} == \
            {(b.padded_size // N,)}
    p2, _, _ = train(hvd.DistributedOptimizer(optax.adam(1e-2),
                                              zero_stage=2),
                     True, x, y, steps=6)
    for k in p2:
        np.testing.assert_allclose(np.asarray(p3[k]), np.asarray(p2[k]),
                                   rtol=1e-4, atol=1e-6)


def test_stage3_overlap_quantized_compose():
    """stage 3 × overlap × quantized: the gradient reduce-scatter rides
    the int8 DCN wire with shard-local EF (residual active), the param
    gather issues through the stream entry points, and training tracks
    the exact-wire stage-3 run."""
    rng = np.random.RandomState(24)
    x, y = make_data(rng)
    tq = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=3,
                                  quantized=True, overlap=True,
                                  num_comm_streams=2)
    pq, _, sq, lq = train3(tq, x, y, steps=6, overlap=True,
                           num_comm_streams=2)
    assert lq[-1] < lq[0]
    assert isinstance(sq, hvd.ZeroState)
    assert sq.gather_residual is None  # no trailing all-gather leg
    rs = [l for l in jax.tree.leaves(sq.residual) if l is not None]
    assert rs and any(float(jnp.abs(l).max()) > 0 for l in rs)
    tf_ = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=3)
    pf, _, _, _ = train3(tf_, x, y, steps=6)
    for k in pf:
        np.testing.assert_allclose(np.asarray(pq[k]), np.asarray(pf[k]),
                                   rtol=0.05, atol=5e-3)


def test_zero3_shard_gather_roundtrip_host():
    """Host-side: shard → gather is the exact identity, plans agree with
    gradient-side plan_buckets, and reshard 8→5→8 / 1→8 / 8→1 round-trip
    the parameters bit-exactly (the world sizes that do NOT divide the
    padded buckets)."""
    params = {"w": jnp.arange(130.0).reshape(130, 1),
              "b": jnp.arange(7.0) * 0.5}
    psh = hvd.zero3_shard_params(params)
    plan = hvd.zero3_plan(params)
    assert [tuple(p.shape) for p in psh] == \
        [(b.padded_size,) for b in plan]
    back = hvd.zero3_gather_params(psh, params)
    for k in params:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]))
    for w_from, w_to in ((8, 5), (1, 8), (8, 1), (5, 3)):
        a = hvd.zero3_reshard_params(
            hvd.zero3_reshard_params(psh, params, from_world=8,
                                     to_world=w_from),
            params, from_world=w_from, to_world=w_to)
        b = hvd.zero3_reshard_params(a, params, from_world=w_to,
                                     to_world=8)
        for s0, s1 in zip(psh, b):
            np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


# --- reshard edge cases (ISSUE 8 satellite) --------------------------------


def test_reshard_worlds_that_do_not_divide():
    """8→5→8, 1→8→1, 8→1→8: world sizes whose lcm padding does not
    divide each other still round-trip every moment leaf bit-exactly
    (the 8→3→8 case lives in test_elastic_reshard_roundtrip)."""
    rng = np.random.RandomState(30)
    x, y = make_data(rng)
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), zero=True)
    _, s1, _ = train(tx, True, x, y, steps=2)
    host = jax.device_get(s1)
    params0 = init_params()
    for w_mid in (5, 1):
        mid = hvd.zero_reshard_state(host, params0, from_world=8,
                                     to_world=w_mid, to_local_size=w_mid)
        plan_m = fusion.plan_buckets(jax.tree.leaves(params0),
                                     shard_multiple=w_mid)
        for l in jax.tree.leaves(mid.inner):
            if getattr(l, "ndim", 0) >= 1:
                assert l.shape[0] in {b.padded_size for b in plan_m}
        back = hvd.zero_reshard_state(mid, params0, from_world=w_mid,
                                      to_world=8, to_local_size=4)
        for a, b in zip(jax.tree.leaves(host.inner),
                        jax.tree.leaves(back.inner)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the w_mid=1 loop above IS the N→1 and 1→N pair: 8→1 collapses to
    # the single-worker padding (lcm(64,1)=64) and 1→8 fans back out


def test_reshard_microbatch_state_rebuilds_at_boundary():
    """Stage-1/stage-2 accumulation state reshards at cycle boundaries:
    bucket-flat shard accumulators (stage 2) remap exactly; leading-axis
    microbatch state (stage-1 full accumulator) rebuilds as zeros at the
    new world with the right shapes."""
    rng = np.random.RandomState(31)
    x, y = make_data(rng)
    params0 = init_params()
    # stage 2: acc_grads is bucket-flat and remaps like a moment
    t2 = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=2,
                                  backward_passes_per_step=2)
    _, s2, _ = train(t2, True, x, y, steps=2)
    host2 = jax.device_get(s2)
    back2 = hvd.zero_reshard_state(
        hvd.zero_reshard_state(host2, params0, from_world=8, to_world=5,
                               to_local_size=5),
        params0, from_world=5, to_world=8, to_local_size=4)
    for a, b in zip(jax.tree.leaves(host2.inner),
                    jax.tree.leaves(back2.inner)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # stage 1: acc is [world, *shape]; at a cycle boundary it is zeros
    # and rebuilds as zeros shaped for the new world
    t1 = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=1,
                                  backward_passes_per_step=2)
    _, s1, _ = train(t1, True, x, y, steps=2)
    host1 = jax.device_get(s1)
    r5 = hvd.zero_reshard_state(host1, params0, from_world=8, to_world=5,
                                to_local_size=5)
    assert isinstance(r5.inner, hvd.ZeroFullMultiStepsState)
    for acc, leaf in zip(r5.inner.acc, jax.tree.leaves(params0)):
        assert tuple(acc.shape) == (5,) + tuple(jnp.shape(leaf))
        assert float(jnp.abs(acc).max()) == 0.0


# --- tape threading --------------------------------------------------------


def test_value_and_grad_zero_returns_locals():
    rng = np.random.RandomState(13)
    xs = rng.randn(N, 3).astype(np.float32)

    def f(p, x):
        return jnp.sum(p * x)

    def spmd(p, x):
        _, g_zero = hvd.value_and_grad(f, zero=True)(p, x[0])
        _, g_red = hvd.value_and_grad(f)(p, x[0])
        # zero=True grads are per-rank locals; reduced grads are the mean
        return g_zero, g_red

    gz, gr = hvd.shard_map(spmd, mesh=hvd.mesh(),
                           in_specs=(P(), P(hvd.HVD_AXES)),
                           out_specs=(P(hvd.HVD_AXES), P()))(
        jnp.ones(3), jnp.asarray(xs))
    np.testing.assert_allclose(np.asarray(gz).reshape(N, 3), xs, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gr), xs.mean(0), rtol=1e-5)
