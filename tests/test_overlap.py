"""Overlapped gradient reduction tests (docs/overlap.md).

Core invariants:
  * ``HOROVOD_OVERLAP=1`` / ``DistributedOptimizer(overlap=True)`` is
    BIT-identical to default-off — the stream schedule reorders
    collective issue only, never bucket contents or per-bucket math
    (SGD-momentum + Adam, 3 steps, 2x4 mesh — the ISSUE acceptance
    criterion);
  * compose matrix: overlap × {quantized+EF, zero, zero+quantized,
    backward_passes_per_step > 1, zero × bpps > 1};
  * the reverse-layer bucket schedule orders buckets by descending max
    leaf index, leaf→bucket assignment untouched;
  * streamed collectives emit ``OVERLAP:*`` timeline spans and account
    ``WireStats.overlap_bytes`` (the numerator of ``hidden_fraction``),
    and ``scripts/obs_report.py`` joins the two artifacts;
  * eager world-of-1 fallback matches the plain optimizer.

All compiled tests run on the 8-device CPU mesh shaped 2x4 so the
hierarchical/DCN decompositions are exercised under the stream schedule.
"""

import importlib.util
import json
import os

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


def train(tx, x, y, steps, bs=16, sspec=None):
    """shard_map DP training with reduce-in-optimizer local gradients
    (the canonical overlap step structure). ``sspec`` is the optimizer
    state's spec tree (device_put with it too); defaults to replicated."""
    params = init_params(x.shape[1])
    state = tx.init(params)
    mesh = hvd.mesh()
    if sspec is None:
        sspec = jax.tree.map(lambda _: P(), state)
    state = jax.device_put(
        state, jax.tree.map(lambda s: NamedSharding(mesh, s), sspec))

    @jax.jit
    def step(params, state, xb, yb):
        def spmd(params, state, xb, yb):
            loss, grads = hvd.value_and_grad(
                loss_fn, reduce=False)(params, (xb, yb))
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


# --- bit-identical parity (the acceptance criterion) -----------------------


@pytest.mark.parametrize("opt", ["sgd", "adam"])
def test_overlap_bit_identical_to_default(opt):
    """overlap=True vs default-off over 3 training steps: identical
    bucket contents + identical per-bucket collectives in a different
    issue order must produce bit-identical parameters. The tiny fusion
    threshold forces a multi-bucket plan so the stream schedule actually
    reorders something."""
    rng = np.random.RandomState(0)
    x, y = make_data(rng)
    mk = (lambda: optax.sgd(0.1, momentum=0.9)) if opt == "sgd" \
        else (lambda: optax.adam(1e-2))
    p_off, _, _ = train(
        hvd.DistributedOptimizer(mk(), fusion_threshold_bytes=16),
        x, y, steps=3)
    p_on, _, _ = train(
        hvd.DistributedOptimizer(mk(), fusion_threshold_bytes=16,
                                 overlap=True, num_comm_streams=2),
        x, y, steps=3)
    for k in p_off:
        np.testing.assert_array_equal(np.asarray(p_on[k]),
                                      np.asarray(p_off[k]))


def test_overlap_env_knob(monkeypatch):
    import dataclasses

    from horovod_tpu.common import basics as B

    cfg = dataclasses.replace(B.config(), overlap=True, num_comm_streams=2)
    monkeypatch.setattr(B._state, "config", cfg)
    rng = np.random.RandomState(1)
    x, y = make_data(rng, n=48)
    p_env, _, _ = train(hvd.DistributedOptimizer(optax.sgd(0.1)),
                        x, y, steps=2)
    monkeypatch.undo()
    p_off, _, _ = train(hvd.DistributedOptimizer(optax.sgd(0.1)),
                        x, y, steps=2)
    for k in p_off:
        np.testing.assert_array_equal(np.asarray(p_env[k]),
                                      np.asarray(p_off[k]))


# --- reverse-layer bucket schedule -----------------------------------------


def test_stream_order_reverse_layer():
    """Buckets issue in descending max-leaf-index order (deepest layers'
    gradients are ready first in backprop) without changing the plan."""
    leaves = [jnp.zeros(100, jnp.float32) for _ in range(10)]
    plan = fusion.plan_buckets(leaves, threshold_bytes=1000)
    assert len(plan) > 2
    order = fusion.stream_order(plan)
    assert sorted(order) == list(range(len(plan)))  # a permutation
    maxes = [max(plan[j].leaf_indices) for j in order]
    assert maxes == sorted(maxes, reverse=True)
    # tree-order plan => stream order is exactly reversed
    assert list(order) == list(range(len(plan)))[::-1]


def test_stream_order_mixed_dtypes_interleaves_globally():
    # Two dtype groups: the schedule orders ACROSS groups by leaf
    # readiness, not group-by-group.
    leaves = [jnp.zeros(4, jnp.float32), jnp.zeros(4, jnp.bfloat16),
              jnp.zeros(4, jnp.float32), jnp.zeros(4, jnp.bfloat16)]
    plan = fusion.plan_buckets(leaves, threshold_bytes=8)
    order = fusion.stream_order(plan)
    maxes = [max(plan[j].leaf_indices) for j in order]
    assert maxes == sorted(maxes, reverse=True)


# --- compose matrix --------------------------------------------------------


def test_overlap_quantized_ef_bit_identical():
    """overlap × quantized+EF: same bucket plan → same scale-block
    boundaries → bit-identical to quantized without overlap."""
    rng = np.random.RandomState(2)
    x, y = make_data(rng)

    def run(overlap):
        tx = hvd.DistributedOptimizer(optax.sgd(0.1), quantized=True,
                                      overlap=overlap)
        st = tx.init(init_params())
        spec = hvd.QuantizedEFState(
            jax.tree.map(lambda _: P(), st.inner),
            jax.tree.map(lambda _: hvd.data_pspec(), st.residual))
        return train(tx, x, y, steps=4, sspec=spec)

    p_on, s_on, _ = run(True)
    p_off, _, _ = run(False)
    for k in p_off:
        np.testing.assert_array_equal(np.asarray(p_on[k]),
                                      np.asarray(p_off[k]))
    # EF residuals became active through the streamed wire too
    assert any(float(jnp.abs(l).max()) > 0
               for l in jax.tree.leaves(s_on.residual))


def test_overlap_zero_bit_identical():
    rng = np.random.RandomState(3)
    x, y = make_data(rng)

    def run(overlap, quantized=False):
        tx = hvd.DistributedOptimizer(optax.adam(1e-2), zero=True,
                                      quantized=quantized, overlap=overlap,
                                      num_comm_streams=2)
        st = tx.init(init_params())
        return train(tx, x, y, steps=3, sspec=hvd.zero_state_pspecs(st))

    for quantized in (False, True):
        p_on, _, _ = run(True, quantized)
        p_off, _, _ = run(False, quantized)
        for k in p_off:
            np.testing.assert_array_equal(np.asarray(p_on[k]),
                                          np.asarray(p_off[k]))


def test_overlap_backward_passes_double_buffer():
    """overlap × backward_passes_per_step=2 (replicated path): the
    double-buffered accumulator — k microbatches then one apply — matches
    one step on the concatenated batch."""
    rng = np.random.RandomState(4)
    x, y = make_data(rng)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), overlap=True,
                                  backward_passes_per_step=2)
    st = tx.init(init_params())
    assert isinstance(st, hvd.OverlapMultiStepsState)
    spec = hvd.overlap_state_pspecs(st)
    pk, sk, _ = train(tx, x, y, steps=2, bs=16, sspec=spec)
    # one big-batch step with the plain optimizer
    p1, _, _ = train(hvd.DistributedOptimizer(optax.sgd(0.1)),
                     x, y, steps=1, bs=32)
    for k in p1:
        np.testing.assert_allclose(np.asarray(pk[k]), np.asarray(p1[k]),
                                   rtol=2e-5, atol=1e-7)
    # mid-cycle state: pending holds the last microbatch's raw grads?
    # after 2 full cycles (2 steps of k=2... each train step is ONE
    # microbatch call), mini_step wrapped correctly
    assert int(jax.device_get(sk.mini_step)) == 2 % 2


def test_overlap_zero_backward_passes_double_buffer():
    """overlap × zero × backward_passes_per_step=2: the shard-level
    double buffer (packed-bucket pending) matches one ZeRO step on the
    concatenated batch, and the accumulator stays 1/world per rank."""
    rng = np.random.RandomState(5)
    x, y = make_data(rng)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), zero=True, overlap=True,
                                  backward_passes_per_step=2)
    st = tx.init(init_params())
    assert isinstance(st.inner, hvd.ZeroOverlapMultiStepsState)
    pk, sk, _ = train(tx, x, y, steps=2, bs=16,
                      sspec=hvd.zero_state_pspecs(st))
    t1 = hvd.DistributedOptimizer(optax.sgd(0.1), zero=True)
    s1 = t1.init(init_params())
    p1, _, _ = train(t1, x, y, steps=1, bs=32,
                     sspec=hvd.zero_state_pspecs(s1))
    for k in p1:
        np.testing.assert_allclose(np.asarray(pk[k]), np.asarray(p1[k]),
                                   rtol=2e-5, atol=1e-7)
    # acc shards are flat buckets sharded 1/world on device
    plan = fusion.plan_buckets(jax.tree.leaves(init_params()),
                               shard_multiple=N)
    acc = jax.tree.leaves(sk.inner.acc_shards)
    assert {l.shape for l in acc} == {(b.padded_size,) for b in plan}
    for l in acc:
        assert {s.data.shape for s in l.addressable_shards} == \
            {(l.shape[0] // N,)}


def test_overlap_presummed_fallback_matches_default():
    """Auto-psummed (jax.value_and_grad) gradients + overlap + bpps>1:
    statically detected, falls back to accumulate-locally semantics —
    same result, no wire blow-up."""
    rng = np.random.RandomState(6)
    x, y = make_data(rng)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), overlap=True,
                                  backward_passes_per_step=2)
    st = tx.init(init_params())
    spec = hvd.overlap_state_pspecs(st)
    mesh = hvd.mesh()
    params = init_params()
    state = jax.device_put(
        st, jax.tree.map(lambda s: NamedSharding(mesh, s), spec))

    @jax.jit
    def step(params, state, xb, yb):
        def spmd(params, state, xb, yb):
            loss, grads = jax.value_and_grad(loss_fn)(params, (xb, yb))
            updates, ns = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), ns

        return hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), spec, P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(), spec))(params, state, xb, yb)

    for i in range(2):
        params, state = step(params, state,
                             jnp.asarray(x[i * 16:(i + 1) * 16]),
                             jnp.asarray(y[i * 16:(i + 1) * 16]))
    p1, _, _ = train(hvd.DistributedOptimizer(optax.sgd(0.1)),
                     x, y, steps=1, bs=32)
    for k in p1:
        np.testing.assert_allclose(np.asarray(params[k]),
                                   np.asarray(p1[k]),
                                   rtol=2e-5, atol=1e-7)


# --- timeline + wire accounting --------------------------------------------


def _trace_overlap_step(**opt_kwargs):
    tx = hvd.DistributedOptimizer(optax.sgd(0.1),
                                  fusion_threshold_bytes=16, **opt_kwargs)
    params = init_params()
    state = tx.init(params)
    mesh = hvd.mesh()
    rng = np.random.RandomState(7)
    x, y = make_data(rng, n=16)

    def spmd(params, state, xb, yb):
        loss, grads = hvd.value_and_grad(
            loss_fn, reduce=False)(params, (xb, yb))
        updates, ns = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), ns, hvd.allreduce(loss)

    f = jax.jit(hvd.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(), P(), P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
        out_specs=(P(), P(), P())))
    with hvd.record_wire_stats() as ws:
        f.lower(params, state, jnp.asarray(x), jnp.asarray(y))
    return ws


def test_timeline_overlap_spans(tmp_path):
    path = str(tmp_path / "tl.json")
    hvd.start_timeline(path)
    try:
        _trace_overlap_step(overlap=True)
    finally:
        hvd.stop_timeline()
    events = json.load(open(path))
    names = {e["name"] for e in events}
    assert any(n.startswith("OVERLAP:ALLREDUCE") for n in names), names
    # spans, not instants: B/E pairs balance per tid (monitor/span_audit
    # raises SpanImbalanceError on any unbalanced or negative depth)
    from horovod_tpu.monitor.span_audit import audit_spans

    audit = audit_spans(events, prefix="OVERLAP", require_spans=True)
    assert audit.balanced


def test_wire_stats_overlap_accounting():
    ws_on = _trace_overlap_step(overlap=True)
    ws_off = _trace_overlap_step(overlap=False)
    # same wire bytes either way (schedule, not traffic, changes)...
    assert ws_on.ici_bytes + ws_on.dcn_bytes == \
        ws_off.ici_bytes + ws_off.dcn_bytes
    # ...but only overlap mode marks them stream-issued
    assert ws_off.overlap_bytes == 0 and ws_off.hidden_fraction == 0.0
    assert ws_on.overlap_bytes > 0
    assert ws_on.streamed_buckets >= 1
    # below 1.0: the loss allreduce is not part of the gradient stream
    assert 0.0 < ws_on.hidden_fraction < 1.0


def test_obs_report_joins_timeline_and_metrics(tmp_path):
    """scripts/obs_report.py over one overlapped step's two artifacts
    (Timeline JSON + metrics JSONL): balanced spans, nonzero ICI bytes,
    no stalls, and the hidden fraction it recomputes from the
    ``comm.wire.*`` gauges is the traced program's own."""
    from horovod_tpu import monitor
    from horovod_tpu.monitor import JsonlSink

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "obs_report.py")
    spec = importlib.util.spec_from_file_location("_obs_report", path)
    obs_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_report)

    tl, jsonl = str(tmp_path / "tl.json"), str(tmp_path / "m.jsonl")
    hvd.start_timeline(tl)
    try:
        ws = _trace_overlap_step(overlap=True)
    finally:
        hvd.stop_timeline()
    JsonlSink(jsonl).write(monitor.metrics().snapshot())

    report = obs_report.build_report(tl, jsonl)
    assert report["spans_balanced"], report["span_imbalance"]
    assert report["total_spans"] > 0
    assert any(k.startswith("OVERLAP:ALLREDUCE")
               for k in report["activity_time_us"])
    wb = report["wire_budget"]
    assert wb["ici_bytes_per_step_device"] == pytest.approx(ws.ici_bytes)
    assert wb["ici_bytes_per_step_device"] > 0
    assert not report["stalls"] and report["stall_warnings"] == 0
    assert report["comm_hidden_fraction"] == pytest.approx(
        ws.hidden_fraction)
    assert 0.0 < report["comm_hidden_fraction"] < 1.0
    assert report["streamed_buckets"] == ws.streamed_buckets


def test_zero_overlap_streams_rs_and_ag():
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), zero=True, overlap=True)
    params = init_params()
    state = tx.init(params)
    mesh = hvd.mesh()
    sspec = hvd.zero_state_pspecs(state)
    rng = np.random.RandomState(8)
    x, y = make_data(rng, n=16)

    def spmd(params, state, xb, yb):
        loss, grads = hvd.value_and_grad(
            loss_fn, reduce=False)(params, (xb, yb))
        updates, ns = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), ns

    f = jax.jit(hvd.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(), sspec, P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
        out_specs=(P(), sspec)))
    with hvd.record_wire_stats() as ws:
        f.lower(params, state, jnp.asarray(x), jnp.asarray(y))
    # both halves of the ZeRO wire (reduce-scatter AND all-gather) ride
    # the stream schedule: everything the step moves is stream-issued
    assert ws.overlap_bytes == pytest.approx(ws.ici_bytes + ws.dcn_bytes)
    assert ws.streamed_buckets >= 2  # >= one RS + one AG


# --- eager world-of-1 fallback ---------------------------------------------


def test_eager_world_of_one_matches_plain_optimizer():
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), overlap=True)
    ref = optax.adam(1e-2)
    params = init_params()
    rng = np.random.RandomState(9)
    x, y = make_data(rng, n=16)
    g = jax.grad(loss_fn)(params, (jnp.asarray(x), jnp.asarray(y)))
    u1, _ = tx.update(g, tx.init(params), params)
    u2, _ = ref.update(g, ref.init(params), params)
    for k in u2:
        np.testing.assert_allclose(np.asarray(u1[k]), np.asarray(u2[k]),
                                   rtol=1e-6, atol=1e-8)


def test_eager_world_of_one_double_buffer_applies_every_k():
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), overlap=True,
                                  backward_passes_per_step=2)
    params = init_params()
    rng = np.random.RandomState(10)
    x, y = make_data(rng, n=16)
    g = jax.grad(loss_fn)(params, (jnp.asarray(x), jnp.asarray(y)))
    state = tx.init(params)
    u, state = tx.update(g, state, params)
    assert all(float(jnp.abs(l).max()) == 0 for l in jax.tree.leaves(u))
    u, state = tx.update(g, state, params)
    assert any(float(jnp.abs(l).max()) > 0 for l in jax.tree.leaves(u))
    # k identical microbatches => the apply uses their mean == g
    ref = optax.sgd(0.1)
    ur, _ = ref.update(g, ref.init(params), params)
    for a, b in zip(jax.tree.leaves(u), jax.tree.leaves(ur)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-8)


# --- autotune integration --------------------------------------------------


def test_tuned_params_override_threads_overlap():
    from horovod_tpu.autotune import TunedParams

    tuned = TunedParams(overlap=True, num_comm_streams=2)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), tuned_params=tuned,
                                  backward_passes_per_step=2)
    # overlap=True + k>1 via the override selects the double-buffered
    # accumulator state
    assert isinstance(tx.init(init_params()), hvd.OverlapMultiStepsState)


def test_autotune_overlap_csv_round_trip(tmp_path):
    from horovod_tpu.autotune import ParameterManager, TunedParams, read_log
    from horovod_tpu.autotune import parameter_manager as pm_mod

    path = str(tmp_path / "at.csv")
    pm = ParameterManager(TunedParams(), warmup_samples=0, max_samples=10,
                          log_path=path, tune_overlap=True, seed=11)
    while not pm.done:
        pm.record_sample(2.0 if pm.current.overlap else 1.0)
    assert "overlap" in pm_mod.CSV_FIELDS
    assert "num_comm_streams" in pm_mod.CSV_FIELDS
    rows = read_log(path)
    assert {r["overlap"] for r in rows} == {False, True}
    for row, (p, _) in zip(rows, pm.history):
        assert row["overlap"] == p.overlap
        assert row["num_comm_streams"] == p.num_comm_streams
        assert p.num_comm_streams in (1, 2, 4)
        if not p.overlap:
            assert p.num_comm_streams == 1  # dead knob pinned
    assert pm.best.overlap is True


def test_autotune_overlap_gate_off_never_proposes():
    from horovod_tpu.autotune import ParameterManager, TunedParams

    pm = ParameterManager(TunedParams(), warmup_samples=0, max_samples=6,
                          seed=12)
    while not pm.done:
        pm.record_sample(1.0)
    assert all(not p.overlap and p.num_comm_streams == 1
               for p, _ in pm.history)


def test_cache_schema_v4_tolerant_from_dict():
    from horovod_tpu.autotune import TunedParams
    from horovod_tpu.autotune import driver as at_driver

    # v12 = the compile-ahead autotune schema (docs/compile.md); the
    # tolerant-read contract below is version-independent.
    assert at_driver._CACHE_VERSION == 12
    assert "v12" in at_driver.cache_key_for("x")
    # v1/v2-era dicts (no overlap keys) stay readable with defaults
    old = {"fusion_threshold_bytes": 1 << 22, "quant_block": 128,
           "hierarchical_allreduce": True}
    p = TunedParams.from_dict(old)
    assert p.overlap is False and p.num_comm_streams == 1
    assert p.zero_stage == 0
    assert TunedParams.from_dict(p.as_dict()) == p
    # v2/v3-era boolean zero_sharding names stage 2 (the PR-4 behavior)
    p = TunedParams.from_dict({**old, "zero_sharding": True})
    assert p.zero_stage == 2 and p.zero_sharding is True
