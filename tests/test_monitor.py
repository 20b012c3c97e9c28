"""Unified observability layer tests (horovod_tpu/monitor/): registry
semantics, sinks, cross-rank aggregation, StallInspector (including the
chaos-stall acceptance scenario), host/device profile correlation, span
audit, the forensic layer (flight recorder ring/dumps/triggers,
straggler attribution with the chaos cross-wiring acceptance scenarios,
link health, postmortem join), and the <1% overhead budgets (registry,
and forensics armed) on the 8-device CPU mesh."""

import importlib.util
import json
import os
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import chaos, monitor
from horovod_tpu.common import counters
from horovod_tpu.monitor import (
    JsonlSink,
    MetricsRegistry,
    PrometheusSink,
    StallInspector,
    audit_spans,
)
from horovod_tpu.monitor.registry import (
    LOG2_BUCKET_BOUNDS,
    NUM_BUCKETS,
    _bucket_index,
)
from horovod_tpu.monitor.span_audit import SpanImbalanceError


# ---------------------------------------------------------------------------
# Registry semantics


class TestRegistry:
    def test_counter_monotone(self):
        r = MetricsRegistry(enabled=True)
        c = r.counter("a.b")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        assert c.value == 3.5

    def test_gauge(self):
        r = MetricsRegistry(enabled=True)
        g = r.gauge("q", role="x")
        g.set(7)
        g.add(-2)
        assert g.value == 5.0

    def test_histogram_log2_buckets(self):
        r = MetricsRegistry(enabled=True)
        h = r.histogram("lat")
        assert _bucket_index(0.5) == 0       # <= 2^0
        assert _bucket_index(1.0) == 0
        assert _bucket_index(2.0) == 1
        assert _bucket_index(3.0) == 2       # 2 < 3 <= 4
        assert _bucket_index(1024.0) == 10
        assert _bucket_index(2.0 ** 40) == NUM_BUCKETS - 1  # +Inf bucket
        for v in (0.5, 3.0, 1024.0):
            h.observe(v)
        assert h.count == 3
        assert h.sum == pytest.approx(1027.5)
        assert h.counts[0] == 1 and h.counts[2] == 1 and h.counts[10] == 1
        assert LOG2_BUCKET_BOUNDS[-1] == float("inf")

    def test_labels_are_identity(self):
        r = MetricsRegistry(enabled=True)
        a = r.counter("c", hop="ici")
        b = r.counter("c", hop="dcn")
        assert a is not b
        assert a is r.counter("c", hop="ici")
        assert a.key == "c{hop=ici}"

    def test_kind_conflict_raises(self):
        r = MetricsRegistry(enabled=True)
        r.counter("x")
        with pytest.raises(ValueError):
            r.gauge("x")

    def test_disabled_registry_noops(self):
        r = MetricsRegistry(enabled=False)
        c = r.counter("n")
        c.inc(5)
        r.histogram("h").observe(1)
        assert c.value == 0.0
        assert r.histogram("h").count == 0

    def test_enabled_is_the_default(self):
        # The acceptance contract: the registry defaults ON.
        assert monitor.metrics_enabled()

    def test_snapshot_and_prefix_filter(self):
        r = MetricsRegistry(enabled=True)
        r.counter("serve.steps").inc(3)
        r.gauge("comm.depth").set(2)
        r.histogram("serve.lat").observe(4)
        snap = r.snapshot()
        assert snap["counters"]["serve.steps"] == 3.0
        assert snap["gauges"]["comm.depth"] == 2.0
        assert snap["histograms"]["serve.lat"]["count"] == 1
        only_serve = r.snapshot(prefix="serve.")
        assert "comm.depth" not in only_serve["gauges"]
        assert "serve.steps" in only_serve["counters"]


# ---------------------------------------------------------------------------
# Sinks


class TestSinks:
    def test_jsonl_sink_roundtrip(self, tmp_path):
        r = MetricsRegistry(enabled=True)
        r.counter("k").inc(2)
        path = str(tmp_path / "m.jsonl")
        sink = JsonlSink(path)
        sink.write(r.snapshot())
        r.counter("k").inc()
        sink.write(r.snapshot())
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2
        assert lines[0]["counters"]["k"] == 2.0
        assert lines[1]["counters"]["k"] == 3.0
        assert lines[1]["kind"] == "metrics"

    def test_prometheus_endpoint(self):
        r = MetricsRegistry(enabled=True)
        r.counter("comm.bytes", hop="ici").inc(128)
        r.gauge("serve.queue_depth").set(4)
        h = r.histogram("lat.ms")
        h.observe(3)
        h.observe(100)
        sink = PrometheusSink(r, port=0)
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{sink.port}/metrics",
                timeout=5).read().decode()
        finally:
            sink.close()
        assert 'horovod_comm_bytes{hop="ici"} 128' in body
        assert "# TYPE horovod_comm_bytes counter" in body
        assert "horovod_serve_queue_depth 4" in body
        # cumulative buckets: the le="4" bucket holds the 3-observation,
        # the +Inf bucket holds both
        assert 'horovod_lat_ms_bucket{le="4"} 1' in body
        assert 'horovod_lat_ms_bucket{le="+Inf"} 2' in body
        assert "horovod_lat_ms_count 2" in body

    def test_timeline_counter_mirror(self, tmp_path):
        path = str(tmp_path / "tl.json")
        hvd.start_timeline(path)
        try:
            monitor.metrics().counter("mirror.test").inc(5)
            monitor.flush()
        finally:
            hvd.stop_timeline()
        events = json.load(open(path))
        mirrors = [e for e in events if e["ph"] == "C"
                   and e["name"] == "METRIC:mirror.test"]
        assert mirrors and mirrors[-1]["args"]["value"] >= 5.0


# ---------------------------------------------------------------------------
# Wire-stats + collective instrumentation


def _traced_allreduce():
    mesh = hvd.mesh()
    f = jax.jit(hvd.shard_map(
        lambda x: hvd.allreduce(x, op=hvd.Sum),
        mesh=mesh, in_specs=P(hvd.HVD_AXES), out_specs=P()))
    with hvd.record_wire_stats() as ws:
        f.lower(jnp.ones((8, 4)))
    return ws


class TestWireInstrumentation:
    def test_traced_bytes_feed_registry(self):
        before = monitor.metrics().counter("comm.bytes", hop="ici").value
        traces_before = monitor.metrics().counter("comm.traces").value
        ws = _traced_allreduce()
        assert ws.ici_bytes > 0
        after = monitor.metrics().counter("comm.bytes", hop="ici").value
        assert after - before == pytest.approx(ws.ici_bytes)
        assert monitor.metrics().counter("comm.traces").value == \
            traces_before + 1
        # the published gauges describe the last traced program
        assert monitor.metrics().gauge("comm.wire.ici_bytes").value == \
            pytest.approx(ws.ici_bytes)

    def test_registry_counts_without_recorder(self):
        # _acct_enabled(): the registry accounts trace-time bytes even
        # with no record_wire_stats context installed.
        before = monitor.metrics().counter("comm.bytes", hop="ici").value
        mesh = hvd.mesh()
        jax.jit(hvd.shard_map(
            lambda x: hvd.allreduce(x, op=hvd.Sum),
            mesh=mesh, in_specs=P(hvd.HVD_AXES), out_specs=P()
        )).lower(jnp.ones((8, 2)))
        assert monitor.metrics().counter(
            "comm.bytes", hop="ici").value > before

    def test_eager_latency_histogram(self):
        h = monitor.metrics().histogram("comm.eager.latency_ms",
                                        kind="allreduce")
        before = h.count
        hvd.allreduce(jnp.ones(3), name="monitor.eager.probe")
        assert h.count == before + 1


# ---------------------------------------------------------------------------
# Cross-rank aggregation


class TestAggregation:
    def test_world_of_one_is_identity(self):
        monitor.metrics().counter("agg.probe").inc(4)
        agg = monitor.aggregate()
        assert agg["world"] == 1
        assert agg["counters"]["agg.probe"] == \
            monitor.metrics().counter("agg.probe").value

    def test_flat_layout_roundtrip_shapes(self):
        r = MetricsRegistry(enabled=True)
        r.counter("c1").inc(1)
        r.gauge("g1").set(2)
        r.histogram("h1").observe(3)
        snap = r.snapshot()
        keys, vals = r._flat_layout(snap)
        assert len(keys) == 3
        # histogram contributes counts + sum + count
        assert len(vals) == 2 + NUM_BUCKETS + 2

    def test_aggregation_survives_elastic_resize(self):
        """Counters persist across the shutdown→init cycle (an elastic
        world transition) and aggregation still works on the new world."""
        marker = monitor.metrics().counter("agg.resize_probe")
        marker.inc(11)
        inc_before = monitor.metrics().counter(
            "elastic.incarnations").value
        hvd.shutdown()
        try:
            hvd.init(mesh_shape=(2, 4))
            assert monitor.metrics().counter(
                "agg.resize_probe").value == 11.0
            agg1 = monitor.aggregate()
            assert agg1["counters"]["agg.resize_probe"] == 11.0
            hvd.shutdown()
            hvd.init(mesh_shape=(1, 8))  # resized world
            marker.inc()
            agg2 = monitor.aggregate()
            assert agg2["counters"]["agg.resize_probe"] == 12.0
            assert monitor.metrics().counter(
                "elastic.incarnations").value >= inc_before + 2
        finally:
            hvd.shutdown()
            hvd.init()


# ---------------------------------------------------------------------------
# StallInspector


class TestStallInspector:
    def test_warning_structure_and_api(self, tmp_path):
        path = str(tmp_path / "tl.json")
        hvd.start_timeline(path)
        insp = StallInspector(warning_secs=0.05)
        try:
            insp.record_start("stalled.tensor", kind="allreduce", rank=0)
            time.sleep(0.08)
            assert [s["name"] for s in insp.stalled()] == ["stalled.tensor"]
            fired = insp.check()
            assert len(fired) == 1
            w = fired[0]
            assert "waiting for remainder of ranks" in w["message"]
            assert "Stalled tensor: stalled.tensor" in w["message"]
            assert "ready ranks: 0" in w["message"]
            assert w["rank"] == 0
            # warned once, not per check
            assert insp.check() == []
            insp.record_done("stalled.tensor")
            assert insp.stalled() == []
        finally:
            hvd.stop_timeline()
        events = json.load(open(path))
        stall_evs = [e for e in events
                     if str(e["name"]).startswith("STALL:")]
        assert stall_evs and stall_evs[0]["ph"] == "i"
        assert stall_evs[0]["args"]["ready_ranks"] == [0]

    def test_watchdog_thread_fires(self):
        insp = StallInspector(warning_secs=0.05, check_interval=0.02)
        insp.start()
        try:
            insp.record_start("bg.tensor")
            time.sleep(0.25)
            assert insp.warnings()
        finally:
            insp.record_done("bg.tensor")
            insp.stop()

    def test_chaos_stall_produces_rank_attributed_warning(
            self, tmp_path, monkeypatch):
        """Acceptance: a deliberately stalled eager collective (chaos
        ``stall`` action) produces a rank-attributed StallInspector
        warning and a STALL:* timeline instant within stall_check_time."""
        from horovod_tpu.monitor import stall as stall_mod

        monkeypatch.setenv("HOROVOD_STALL_CHECK_TIME_SECONDS", "0.2")
        hvd.shutdown()
        counters.reset_all()
        try:
            hvd.init()
            insp = stall_mod.stall_inspector()
            assert insp.warning_secs == 0.2  # config reached the watchdog
            n_before = len(insp.warnings())
            path = str(tmp_path / "tl.json")
            hvd.start_timeline(path)
            chaos.configure(chaos.FaultPlan().add(
                "collective.eager", action="stall", secs=1.0))
            warn_count = monitor.metrics().counter(
                "stall.warnings", kind="allreduce").value
            try:
                hvd.allreduce(jnp.ones(2), name="stalled.probe")
            finally:
                chaos.configure(None)
                hvd.stop_timeline()
            new = insp.warnings()[n_before:]
            assert new, "no stall warning fired during the injected stall"
            w = new[-1]
            assert w["name"] == "stalled.probe"
            assert w["rank"] == 0 and 0 in w["ready_ranks"]
            # fired while the op was still stalled — i.e. within
            # stall_check_time of crossing the threshold, not after the
            # 1 s injected stall completed
            assert w["elapsed_secs"] < 0.9
            assert monitor.metrics().counter(
                "stall.warnings", kind="allreduce").value > warn_count
            events = json.load(open(path))
            stall_evs = [e for e in events
                         if e["name"] == "STALL:stalled.probe"]
            assert stall_evs and stall_evs[0]["ph"] == "i"
            assert stall_evs[0]["args"]["rank"] == 0
            # after completion the op is no longer in flight
            assert not any(s["name"] == "stalled.probe"
                           for s in hvd.stalled_tensors())
        finally:
            chaos.reset()
            monkeypatch.delenv("HOROVOD_STALL_CHECK_TIME_SECONDS",
                               raising=False)
            hvd.shutdown()
            hvd.init()

    def test_serve_request_tracking_clears(self):
        from horovod_tpu.models import gpt_tiny
        from horovod_tpu.models.gpt import GPT
        from horovod_tpu.serve import PageConfig
        from horovod_tpu.serve.engine import GenerationEngine, VirtualClock
        from horovod_tpu.serve.scheduler import Request

        cfg = gpt_tiny(num_heads=2, num_layers=1, d_model=16,
                       vocab_size=32)
        params = GPT(cfg).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))["params"]
        pc = PageConfig(num_pages=9, page_size=4, max_slots=2,
                        pages_per_slot=4, num_layers=cfg.num_layers,
                        num_heads=cfg.num_heads,
                        head_dim=cfg.d_model // cfg.num_heads)
        eng = GenerationEngine(cfg, params, pc, eos_id=1)
        steps_before = monitor.metrics().counter("serve.steps").value
        eng.run([Request(prompt=[5, 6, 7], max_new_tokens=3)],
                clock=VirtualClock())
        assert monitor.metrics().counter("serve.steps").value > steps_before
        # every tracked request was untracked on eviction
        from horovod_tpu.monitor.stall import stall_inspector

        assert not any(n.startswith("serve.req")
                       for n in stall_inspector().in_flight())


# ---------------------------------------------------------------------------
# Counters mirror + chaos monotonicity


class TestCounterMirror:
    def test_fault_counters_mirror_into_registry(self):
        before = monitor.metrics().counter("mirror.fault.probe").value
        counters.increment("mirror.fault.probe")
        assert monitor.metrics().counter(
            "mirror.fault.probe").value == before + 1

    def test_counters_stay_monotone_under_chaos(self):
        """With chaos faults active every registry counter must stay
        monotone — sampled across a run of dropping/succeeding eager
        collectives (the acceptance invariant for chaotic runs)."""
        chaos.configure(chaos.FaultPlan().add(
            "collective.eager", action="drop", every=2))
        try:
            reg = monitor.metrics()
            last = {}
            for i in range(8):
                try:
                    hvd.allreduce(jnp.ones(2), name=f"monotone.{i}")
                except Exception:
                    pass  # injected drop
                snap = reg.snapshot()
                for k, v in snap["counters"].items():
                    assert v >= last.get(k, 0.0), \
                        f"counter {k} decreased: {last.get(k)} -> {v}"
                last.update(snap["counters"])
            assert last.get("chaos.drop", 0) >= 1
        finally:
            chaos.reset()


# ---------------------------------------------------------------------------
# Overhead budget (acceptance: <1% of the 8-device CPU mesh step)


class TestOverhead:
    def test_registry_overhead_under_one_percent_of_step(self):
        """The per-step registry work the framework does (a bounded
        handful of counter/gauge/histogram updates — everything else is
        trace-time) must cost <1% of a real 8-device-mesh step."""
        mesh = hvd.mesh()
        tx = hvd.DistributedOptimizer(__import__("optax").sgd(0.01))
        # A bench-representative step (4-layer 512-wide MLP, batch 8/rank)
        # rather than a toy matmul: the budget is a FRACTION of step time,
        # so the denominator must look like a real training step.
        params = {f"w{i}": jnp.full((512, 512), 0.01) for i in range(4)}
        state = tx.init(params)

        def loss_fn(p, x):
            h = x
            for i in range(4):
                h = jnp.tanh(h @ p[f"w{i}"])
            return jnp.mean(h ** 2)

        def spmd(p, s, x):
            loss, grads = jax.value_and_grad(loss_fn)(p, x)
            updates, ns = tx.update(grads, s, p)
            import optax
            return optax.apply_updates(p, updates), ns, hvd.allreduce(loss)

        step = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(), P(hvd.HVD_AXES)),
            out_specs=(P(), P(), P())))
        x = jnp.ones((64, 512))
        params, state, loss = step(params, state, x)  # compile
        jax.block_until_ready(loss)
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            params, state, loss = step(params, state, x)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
        step_secs = float(np.median(times))

        reg = monitor.metrics()
        c = reg.counter("overhead.probe")
        g = reg.gauge("overhead.gauge")
        h = reg.histogram("overhead.hist")
        n = 3000
        t0 = time.perf_counter()
        for i in range(n):
            c.inc()
            g.set(i)
            h.observe(i)
        per_update_trio = (time.perf_counter() - t0) / n
        # generous per-step budget: 20 counter+gauge+histogram trios
        overhead = 20 * per_update_trio
        assert overhead < 0.01 * step_secs, (
            f"registry overhead {overhead * 1e6:.1f}us vs step "
            f"{step_secs * 1e6:.1f}us "
            f"({100 * overhead / step_secs:.2f}% >= 1%)")


# ---------------------------------------------------------------------------
# profile_window


class TestProfileWindow:
    def test_window_brackets_trace_and_timeline(self, tmp_path):
        path = str(tmp_path / "tl.json")
        logdir = str(tmp_path / "prof")
        hvd.start_timeline(path)
        f = jax.jit(lambda x: x * 2)
        try:
            with hvd.profile_window(3, logdir=logdir) as win:
                for _ in win.steps():
                    jax.block_until_ready(f(jnp.ones(4)))
        finally:
            hvd.stop_timeline()
        assert len(win.step_times_ms) == 3
        assert os.path.isdir(logdir)
        events = json.load(open(path))
        audit = audit_spans(events, prefix="PROFILE", require_spans=True)
        assert audit.count["PROFILE:STEP"] == 3
        assert audit.count["PROFILE:WINDOW"] == 1
        assert audit.instants.get("PROFILE:START") == 1
        assert audit.instants.get("PROFILE:STOP") == 1


# ---------------------------------------------------------------------------
# span_audit unit


class TestSpanAudit:
    def test_balanced_with_durations(self):
        events = [
            {"name": "A", "ph": "B", "tid": "t1", "ts": 0.0},
            {"name": "A", "ph": "E", "tid": "t1", "ts": 10.0},
            {"name": "B", "ph": "B", "tid": "t2", "ts": 5.0},
            {"name": "B", "ph": "E", "tid": "t2", "ts": 6.0},
            {"name": "N", "ph": "i", "tid": "t1", "ts": 7.0},
        ]
        audit = audit_spans(events)
        assert audit.balanced
        assert audit.total_spans == 2
        assert audit.duration_us == {"A": 10.0, "B": 1.0}
        assert audit.instants == {"N": 1}

    def test_unclosed_span_raises(self):
        events = [{"name": "A", "ph": "B", "tid": "t", "ts": 0.0}]
        with pytest.raises(SpanImbalanceError):
            audit_spans(events)
        audit = audit_spans(events, require_balanced=False)
        assert not audit.balanced and audit.open_depth == {"t": 1}

    def test_negative_depth_raises(self):
        events = [{"name": "A", "ph": "E", "tid": "t", "ts": 0.0}]
        with pytest.raises(SpanImbalanceError):
            audit_spans(events)

    def test_prefix_and_require_spans(self):
        events = [
            {"name": "X:1", "ph": "B", "tid": "t", "ts": 0.0},
            {"name": "X:1", "ph": "E", "tid": "t", "ts": 1.0},
        ]
        assert audit_spans(events, prefix="X").total_spans == 1
        with pytest.raises(SpanImbalanceError):
            audit_spans(events, prefix="Y", require_spans=True)

    def test_by_phase_grouping(self):
        events = [
            {"name": "X:a", "ph": "B", "tid": "t", "ts": 0.0},
            {"name": "X:a", "ph": "E", "tid": "t", "ts": 2.0},
            {"name": "X:b", "ph": "B", "tid": "t", "ts": 2.0},
            {"name": "X:b", "ph": "E", "tid": "t", "ts": 5.0},
        ]
        assert audit_spans(events).by_phase() == {"X": 5.0}


# ---------------------------------------------------------------------------
# Flight recorder (monitor/flight.py)


from horovod_tpu.monitor.flight import FlightRecorder  # noqa: E402
from horovod_tpu.monitor.span_audit import (  # noqa: E402
    KNOWN_PREFIXES,
    UnknownSpanPrefixError,
    event_prefix,
)
from horovod_tpu.monitor.straggler import StragglerDetector  # noqa: E402


def _load_postmortem():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "postmortem.py")
    spec = importlib.util.spec_from_file_location("_postmortem", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestFlightRecorder:
    def test_ring_is_bounded_and_ordered(self):
        fr = FlightRecorder(capacity=8, snapshot_every=0)
        for i in range(20):
            fr.record(f"FLIGHT:E{i}", tid="t")
        evs = fr.events()
        assert len(evs) == 8
        assert [e["name"] for e in evs] == \
            [f"FLIGHT:E{i}" for i in range(12, 20)]
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs) and seqs[-1] == 19
        assert all("wall" in e for e in evs)

    def test_capacity_zero_disables(self, tmp_path):
        fr = FlightRecorder(capacity=0)
        fr.record("FLIGHT:X")
        assert fr.events() == []
        assert fr.dump(directory=str(tmp_path)) is None

    def test_periodic_registry_snapshots(self):
        monitor.metrics().counter("flight.snap_probe").inc(3)
        fr = FlightRecorder(capacity=64, snapshot_every=4)
        for i in range(10):
            fr.record(f"FLIGHT:S{i}")
        snaps = [e for e in fr.events() if e["name"] == "FLIGHT:SNAPSHOT"]
        assert len(snaps) == 2  # after events 4 and 8
        assert snaps[0]["args"]["counters"]["flight.snap_probe"] >= 3.0

    def test_dump_atomic_crc_and_contents(self, tmp_path):
        import zlib

        fr = FlightRecorder(capacity=32, snapshot_every=0)
        fr.record("FLIGHT:A", args={"k": 1})
        fr.mark_step(7, {"compute": 12.5})
        path = fr.dump("unit", directory=str(tmp_path),
                       extra={"note": "x"})
        assert path and os.path.exists(path)
        assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]
        d = json.load(open(path))
        assert d["kind"] == "flight_record" and d["reason"] == "unit"
        assert d["extra"] == {"note": "x"}
        assert d["identity"]["pid"] == os.getpid()
        names = [e["name"] for e in d["events"]]
        assert names == ["FLIGHT:A", "FLIGHT:STEP"]
        assert d["events"][1]["args"]["step"] == 7
        payload = json.dumps(d["events"], sort_keys=True).encode()
        want = f"crc32:{zlib.crc32(payload) & 0xFFFFFFFF:08x}"
        assert d["events_crc32"] == want
        assert "registry" in d and "in_flight" in d

    def test_dump_without_destination_is_noop(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_FLIGHT_RECORDER_DIR", raising=False)
        fr = FlightRecorder(capacity=8)
        fr.record("FLIGHT:Y")
        assert fr.dump("nowhere") is None

    def test_timeline_events_are_tapped(self, tmp_path):
        from horovod_tpu.monitor import flight as flight_mod

        fr = monitor.flight_recorder()
        hvd.start_timeline(str(tmp_path / "tl.json"))
        try:
            hvd.mesh()  # ensure initialized
            from horovod_tpu.common import basics

            basics._state.timeline.instant("FAULT:tap.probe",
                                           tid="faults")
        finally:
            hvd.stop_timeline()
        assert any(e["name"] == "FAULT:tap.probe"
                   for e in fr.events())
        assert flight_mod.recorder() is fr

    def test_eager_collective_and_stall_reach_ring(self):
        fr = monitor.flight_recorder()
        hvd.allreduce(jnp.ones(2), name="flight.eager.probe")
        colls = [e for e in fr.events()
                 if e["name"] == "FLIGHT:COLLECTIVE"
                 and e["args"]["name"] == "flight.eager.probe"]
        assert colls and colls[-1]["args"]["kind"] == "allreduce"
        # a stall instant lands in the ring even with no timeline
        insp = StallInspector(warning_secs=0.01)
        insp.record_start("flight.stall.probe", rank=0)
        time.sleep(0.03)
        insp.check()
        assert any(e["name"] == "STALL:flight.stall.probe"
                   for e in fr.events())

    def test_excepthook_dump_in_subprocess(self, tmp_path):
        import subprocess
        import sys as _sys

        code = (
            "import os\n"
            "import horovod_tpu as hvd\n"
            "hvd.init()\n"
            "import jax.numpy as jnp\n"
            "hvd.allreduce(jnp.ones(2), name='pre.crash')\n"
            "raise RuntimeError('forensic boom')\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   HOROVOD_FLIGHT_RECORDER_DIR=str(tmp_path))
        env.pop("HOROVOD_TIMELINE", None)
        p = subprocess.run([_sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert p.returncode != 0
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_") and f.endswith(".json")]
        assert dumps, (p.stdout, p.stderr)
        d = json.load(open(os.path.join(tmp_path, dumps[0])))
        assert d["reason"] == "exception"
        assert d["extra"]["exc_type"] == "RuntimeError"
        assert "forensic boom" in d["extra"]["exc"]
        assert any(e["name"] == "FLIGHT:COLLECTIVE"
                   for e in d["events"])

    def test_sigterm_dump_in_subprocess(self, tmp_path):
        import signal
        import subprocess
        import sys as _sys

        code = (
            "import os, signal, time\n"
            "import horovod_tpu as hvd\n"
            "hvd.init()\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "time.sleep(10)\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   HOROVOD_FLIGHT_RECORDER_DIR=str(tmp_path))
        p = subprocess.run([_sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        # delivery semantics preserved: the process still dies of SIGTERM
        assert p.returncode == -signal.SIGTERM or p.returncode == 143
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_") and f.endswith(".json")]
        assert dumps, (p.stdout, p.stderr)
        d = json.load(open(os.path.join(tmp_path, dumps[0])))
        assert d["reason"] == "sigterm"

    def test_explicit_dump_api(self, tmp_path):
        path = str(tmp_path / "explicit.json")
        got = hvd.dump_flight_record(path=path)
        assert got == path
        d = json.load(open(path))
        assert d["reason"] == "explicit"
        assert d["identity"]["world"] >= 1


# ---------------------------------------------------------------------------
# Straggler attribution (monitor/straggler.py)


def _rank_farm(world=4, registry=None, **kw):
    """One detector per emulated rank over ONE shared registry — each
    writes only its own rank's slots, exactly what the fused-allreduce
    SUM reconstructs in a real multi-process world."""
    reg = registry or MetricsRegistry(enabled=True)
    dets = [StragglerDetector(reg, world=world, rank=r, **kw)
            for r in range(world)]
    return reg, dets


class TestStragglerDetection:
    def test_clean_run_zero_false_positives(self):
        reg, dets = _rank_farm(world=4)
        for step in range(10):
            for r, det in enumerate(dets):
                det.record_phase("compute", 100.0 + 0.3 * r)
                det.record_phase("wire.dcn", 10.0 + 0.1 * step)
                det.end_step(step)
            assert dets[0].detect(snapshot=reg.snapshot()) == []
        assert not any(k.startswith("straggler.detected")
                       for k in reg.snapshot()["counters"])

    def test_pp_bubble_clean_run_zero_false_positives(self):
        """Rank-uniform zb idle ticks (the schedule table is geometry-
        determined) with uniform fill credit must never flag: the
        pp_bubble phase is identical across ranks."""
        from horovod_tpu.monitor import straggler as straggler_mod
        reg, dets = _rank_farm(world=4)
        # zb1 on (S=2, M=8): 2 idle ticks of 50 total, half of them
        # filled by ZeRO-3 flights on every rank.
        for step in range(10):
            for r, det in enumerate(dets):
                det.record_phase("compute", 100.0 + 0.3 * r)
                ms = straggler_mod.record_pp_bubble(
                    idle_ticks=2, ticks=50, step_ms=100.0,
                    filled_ticks=1, detector=det)
                assert ms == pytest.approx(100.0 * 1 / 50)
                det.end_step(step)
            assert dets[0].detect(snapshot=reg.snapshot()) == []
        assert not any(k.startswith("straggler.detected")
                       for k in reg.snapshot()["counters"])

    def test_pp_bubble_fill_credit_math(self):
        from horovod_tpu.monitor import straggler as straggler_mod
        reg, dets = _rank_farm(world=4)
        det = dets[0]
        # fully filled bubble charges nothing
        assert straggler_mod.record_pp_bubble(
            4, 40, 200.0, filled_ticks=4, detector=det) == 0.0
        # credit is capped at the measured idle ticks
        assert straggler_mod.record_pp_bubble(
            4, 40, 200.0, filled_ticks=99, detector=det) == 0.0
        # no credit charges the full idle fraction
        assert straggler_mod.record_pp_bubble(
            4, 40, 200.0, detector=det) == pytest.approx(20.0)
        # degenerate inputs clamp instead of raising
        assert straggler_mod.record_pp_bubble(
            -1, 0, 200.0, filled_ticks=-5, detector=det) == 0.0

    def test_pp_bubble_starved_rank_attributed(self):
        """One rank whose flights starve (no fill credit) surfaces as a
        pp_bubble outlier through the ordinary median/MAD gate."""
        from horovod_tpu.monitor import straggler as straggler_mod
        reg, dets = _rank_farm(world=4)
        for r, det in enumerate(dets):
            det.record_phase("compute", 100.0)
            straggler_mod.record_pp_bubble(
                idle_ticks=8, ticks=40, step_ms=100.0,
                filled_ticks=(0 if r == 2 else 8), detector=det)
            det.end_step(0)
        found = dets[0].detect(snapshot=reg.snapshot())
        assert [(d["rank"], d["phase"]) for d in found] == \
            [(2, "pp_bubble")]

    def test_delayed_rank_detected_and_attributed(self):
        reg, dets = _rank_farm(world=4)
        flagged_at = None
        for step in range(3):
            for r, det in enumerate(dets):
                det.record_phase("compute", 100.0)
                det.record_phase(
                    "wire.dcn", 10.0 + (80.0 if r == 2 else 0.0))
                det.end_step(step)
            found = dets[0].detect(snapshot=reg.snapshot())
            if found and flagged_at is None:
                flagged_at = step
                assert [(d["rank"], d["phase"]) for d in found] == \
                    [(2, "wire.dcn")]
        # bounded step count: attributed on the very first detect pass
        assert flagged_at == 0
        snap = reg.snapshot()
        assert snap["counters"][
            "straggler.detected{phase=wire.dcn,rank=2}"] >= 1
        assert snap["gauges"]["step.skew_ms{phase=wire.dcn}"] == \
            pytest.approx(80.0)
        # history rides the flight dump
        assert any(d["rank"] == 2 for d in dets[0].history())

    def test_fewer_than_three_ranks_never_flags(self):
        reg, dets = _rank_farm(world=2)
        for r, det in enumerate(dets):
            det.record_phase("compute", 100.0 + 500.0 * r)
            det.end_step(0)
        assert dets[0].detect(snapshot=reg.snapshot()) == []
        # the skew gauge still publishes for operators
        assert reg.snapshot()["gauges"][
            "step.skew_ms{phase=compute}"] > 0

    def test_detection_emits_straggler_instant(self, tmp_path):
        path = str(tmp_path / "tl.json")
        reg, dets = _rank_farm(world=3)
        for r, det in enumerate(dets):
            det.record_phase("ckpt", 5.0 + (200.0 if r == 1 else 0.0))
            det.end_step(0)
        hvd.start_timeline(path)
        try:
            found = dets[0].detect(snapshot=reg.snapshot())
        finally:
            hvd.stop_timeline()
        assert found and found[0]["rank"] == 1
        events = json.load(open(path))
        evs = [e for e in events if e["name"] == "STRAGGLER:CKPT"]
        assert evs and evs[0]["ph"] == "i"
        assert evs[0]["args"]["rank"] == 1
        assert event_prefix(evs[0]["name"]) in KNOWN_PREFIXES

    def test_phase_gauges_ride_registry_aggregation_schema(self):
        """Every rank pre-creates the full (phase, rank) matrix, so the
        flat aggregation layout is identical across ranks (the
        schema-digest contract of MetricsRegistry.aggregate)."""
        layouts = []
        for r in range(3):
            reg = MetricsRegistry(enabled=True)
            det = StragglerDetector(reg, world=3, rank=r)
            det.record_phase("compute", 10.0 * (r + 1))
            det.end_step(0)
            keys, _ = reg._flat_layout(reg.snapshot())
            layouts.append(keys)
        assert layouts[0] == layouts[1] == layouts[2]

    def test_chaos_delay_attributed_through_real_eager_path(
            self, monkeypatch):
        """Acceptance (chaos cross-wiring): a seeded ``delay`` fault on
        one rank's eager collectives is detected and attributed to that
        (rank, wire.dcn) within a bounded step count, with zero false
        positives on the clean control run."""
        from horovod_tpu.monitor import straggler as straggler_mod

        def drive(inject_rank, reg, dets, steps=2):
            found_all = []
            for step in range(steps):
                for r, det in enumerate(dets):
                    # route the global-path record_phase of
                    # _eager_instrumented to this emulated rank
                    monkeypatch.setattr(straggler_mod, "_global", det)
                    if r == inject_rank:
                        chaos.configure(chaos.FaultPlan(seed=9).add(
                            "collective.eager", "delay", secs=0.12))
                    try:
                        hvd.allreduce(jnp.ones(2),
                                      name=f"cw.{step}.{r}")
                    finally:
                        chaos.configure(None)
                    det.record_phase("compute", 50.0)
                    det.end_step(step)
                found_all += dets[0].detect(snapshot=reg.snapshot())
            return found_all

        try:
            reg, dets = _rank_farm(world=4)
            found = drive(2, reg, dets)
            assert found, "injected delay was never detected"
            assert {(d["rank"], d["phase"]) for d in found} == \
                {(2, "wire.dcn")}
            # clean control: no injection, nothing may fire
            reg2, dets2 = _rank_farm(world=4)
            assert drive(None, reg2, dets2) == []
        finally:
            chaos.reset()
            straggler_mod._reset_for_tests()


class TestLinkHealth:
    def test_degraded_link_flagged_and_recommends_recalibration(
            self, caplog):
        import logging as _logging

        reg = MetricsRegistry(enabled=True)
        det = StragglerDetector(reg, world=1, rank=0,
                                link_drift_gate=1.5, patience=2)
        from horovod_tpu.plan import cost

        predicted = cost.predict_hop_ms("dcn", 1e9)
        with caplog.at_level(_logging.WARNING,
                             logger="horovod_tpu.straggler"):
            # persistently 3x slower than the model predicts
            r1 = det.observe_wire("dcn", 1e9, predicted * 3.0)
            assert r1 == pytest.approx(3.0, rel=0.01)
            assert not reg.snapshot()["counters"].get(
                "straggler.link_degraded{hop=dcn}")  # patience not met
            det.observe_wire("dcn", 1e9, predicted * 3.0)
        snap = reg.snapshot()
        assert snap["counters"]["straggler.link_degraded{hop=dcn}"] == 1
        assert snap["gauges"]["link.health{hop=dcn}"] == \
            pytest.approx(3.0, rel=0.01)
        assert any(d["kind"] == "link" for d in det.history())
        assert any("calibrate_links" in r.message for r in caplog.records)

    def test_healthy_link_never_flags(self):
        reg = MetricsRegistry(enabled=True)
        det = StragglerDetector(reg, world=1, rank=0,
                                link_drift_gate=1.5, patience=2)
        from horovod_tpu.plan import cost

        for _ in range(6):
            det.observe_wire("ici", 1e8,
                             cost.predict_hop_ms("ici", 1e8) * 1.05)
        snap = reg.snapshot()
        assert "straggler.link_degraded{hop=ici}" not in snap["counters"]
        assert snap["gauges"]["link.health{hop=ici}"] == \
            pytest.approx(1.05, rel=0.01)

    def test_recovery_resets_patience(self):
        reg = MetricsRegistry(enabled=True)
        det = StragglerDetector(reg, world=1, rank=0,
                                link_drift_gate=1.5, patience=3)
        from horovod_tpu.plan import cost

        p = cost.predict_hop_ms("pod", 1e8)
        # transient blips that recover below the gate between drifts
        # never accumulate the 3 consecutive over-gate observations
        for _ in range(3):
            det.observe_wire("pod", 1e8, p * 2.0)   # EWMA over the gate
            det.observe_wire("pod", 1e8, p * 0.4)   # EWMA back under
        assert "straggler.link_degraded{hop=pod}" not in \
            reg.snapshot()["counters"]


# ---------------------------------------------------------------------------
# Span-audit vocabulary table (strict mode)


class TestSpanVocabulary:
    def test_known_prefixes_cover_the_documented_table(self):
        for p in ("FAULT", "AUTOTUNE", "OVERLAP", "SERVE", "STALL",
                  "METRIC", "PROFILE", "CYCLE_START", "CKPT", "PP",
                  "STRAGGLER", "FLIGHT"):
            assert p in KNOWN_PREFIXES

    def test_event_prefix(self):
        assert event_prefix("OVERLAP:ALLREDUCE") == "OVERLAP"
        assert event_prefix("CYCLE_START") == "CYCLE_START"

    def test_strict_rejects_unknown_prefix(self):
        events = [
            {"name": "PP:F", "ph": "B", "tid": "t", "ts": 0.0},
            {"name": "PP:F", "ph": "E", "tid": "t", "ts": 1.0},
            {"name": "TYPO:OOPS", "ph": "i", "tid": "t", "ts": 2.0},
        ]
        audit_spans(events, prefix="PP:")  # non-strict: fine
        with pytest.raises(UnknownSpanPrefixError, match="TYPO"):
            audit_spans(events, prefix="PP:", strict=True)

    def test_strict_accepts_full_vocabulary(self):
        events = [{"name": f"{p}:X", "ph": "i", "tid": "t", "ts": 0.0}
                  for p in sorted(KNOWN_PREFIXES - {"CYCLE_START"})]
        events.append({"name": "CYCLE_START", "ph": "i", "tid": "c",
                       "ts": 1.0})
        audit = audit_spans(events, strict=True)
        assert sum(audit.instants.values()) == len(events)


# ---------------------------------------------------------------------------
# Prometheus ephemeral-port discovery (lifecycle satellite)


class TestPrometheusDiscovery:
    def test_ephemeral_port_published_and_discoverable(
            self, tmp_path, monkeypatch):
        from horovod_tpu.monitor import lifecycle

        jsonl = str(tmp_path / "m.jsonl")
        monkeypatch.setenv("HOROVOD_METRICS_PORT", "0")
        monkeypatch.setenv("HOROVOD_METRICS_JSONL", jsonl)
        hvd.shutdown()
        try:
            hvd.init()
            port = lifecycle.prometheus_port()
            assert port and port > 0
            assert monitor.metrics().gauge("metrics.port").value == port
            disc = json.load(open(jsonl + ".port"))
            assert disc["port"] == port
            assert disc["pid"] == os.getpid()
            assert disc["endpoint"].endswith(f":{port}/metrics")
            body = urllib.request.urlopen(disc["endpoint"],
                                          timeout=5).read().decode()
            assert "horovod_" in body
        finally:
            hvd.shutdown()
            lifecycle._reset_for_tests()
            monkeypatch.delenv("HOROVOD_METRICS_PORT")
            monkeypatch.delenv("HOROVOD_METRICS_JSONL")
            hvd.init()


# ---------------------------------------------------------------------------
# Postmortem join (scripts/postmortem.py)


def _write_dump(directory, rank, reason, steps, *, world=3,
                extra_events=(), straggler=(), corrupt=False):
    import zlib

    events = [{"name": "FLIGHT:STEP", "ph": "i", "tid": "flight",
               "wall": 1000.0 + s, "seq": s, "args": {"step": s}}
              for s in range(steps + 1)]
    events += list(extra_events)
    payload = json.dumps(events, sort_keys=True).encode()
    dump = {
        "version": 1, "kind": "flight_record", "reason": reason,
        "ts": 2000.0 + rank,
        "identity": {"rank": rank, "world": world, "pid": 100 + rank,
                     "hostname": f"host{rank}", "local_rank": "0"},
        "events": events,
        "events_crc32":
            f"crc32:{zlib.crc32(payload) & 0xFFFFFFFF:08x}",
        "registry": None, "in_flight": [], "stalled": [],
        "straggler": list(straggler),
    }
    if corrupt:
        dump["events_crc32"] = "crc32:deadbeef"
    path = os.path.join(directory, f"flight_rank{rank}_pid{100+rank}_"
                                   f"000.json")
    with open(path, "w") as f:
        json.dump(dump, f)
    return path


class TestPostmortem:
    def test_join_names_crashing_rank_and_divergence(self, tmp_path):
        pm = _load_postmortem()
        d = str(tmp_path)
        _write_dump(d, 0, "elastic.reset", steps=7)
        _write_dump(d, 1, "elastic.reset", steps=7)
        _write_dump(d, 2, "chaos.crash", steps=4, extra_events=[
            {"name": "FAULT:chaos.crash", "ph": "i", "tid": "faults",
             "wall": 1100.0, "seq": 99}],
            straggler=[{"kind": "phase", "rank": 2, "phase": "wire.dcn",
                        "ms": 90.0, "median_ms": 10.0, "ts": 999.0}])
        report = pm.build_report(d)
        assert report["dumps"] == 3 and not report["corrupt"]
        assert report["crashed_ranks"] == ["rank2"]
        assert report["last_common_step"] == 4
        assert report["max_step"] == 7
        assert report["divergence_step"] == 5
        assert report["diverged_ranks"] == ["rank2"]
        assert report["ranks"]["rank2"]["faults"] == {"chaos.crash": 1}
        assert report["straggler_history"][0]["phase"] == "wire.dcn"
        # the human report renders without crashing
        pm.print_report(report)

    def test_corrupt_dump_rejected_not_trusted(self, tmp_path):
        pm = _load_postmortem()
        d = str(tmp_path)
        _write_dump(d, 0, "exception", steps=3)
        bad = _write_dump(d, 1, "exception", steps=9, corrupt=True)
        report = pm.build_report(d)
        assert report["dumps"] == 1
        assert [c["path"] for c in report["corrupt"]] == [bad]
        # the torn rank-1 file must not have moved last_common_step
        assert report["last_common_step"] == 3

    def test_empty_dir_exits_2(self, tmp_path, capsys):
        pm = _load_postmortem()
        import sys as _sys

        argv = _sys.argv
        _sys.argv = ["postmortem.py", "--dir", str(tmp_path)]
        try:
            assert pm.main() == 2
        finally:
            _sys.argv = argv


# ---------------------------------------------------------------------------
# Chaos cross-wiring: injected crash → parseable dumps on every rank →
# postmortem names the crashing rank (the elastic-driver harness of
# tests/test_elastic_integration.py, with forensics armed).


class TestCrashForensicsIntegration:
    @pytest.mark.chaos
    def test_chaos_crash_leaves_dumps_on_every_rank(self, tmp_path):
        import shlex
        import subprocess  # noqa: F401  (documents the child mechanism)
        import sys as _sys

        from horovod_tpu import chaos as chaos_mod
        from horovod_tpu.common import counters as counters_mod
        from horovod_tpu.elastic import constants
        from horovod_tpu.elastic.discovery import HostDiscoveryScript
        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.runner import safe_shell_exec

        chaos_mod.reset()
        counters_mod.reset_all()
        constants.DISCOVER_HOSTS_FREQUENCY_SECS = 0.25
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker = os.path.join(repo, "tests", "elastic_worker.py")
        flight_dir = str(tmp_path / "flight")
        script = tmp_path / "discover.sh"
        script.write_text("#!/bin/sh\necho hostA:2\necho hostB:1\n")
        script.chmod(0o755)
        log_file = str(tmp_path / "log.jsonl")
        plan = chaos_mod.FaultPlan(seed=23).add(
            "collective.eager", "crash", where="hostB:0", after=3,
            max_count=1)

        driver = ElasticDriver(HostDiscoveryScript(str(script), 1),
                               min_np=2, max_np=3,
                               controller_addr_override="127.0.0.1")

        def _exec(slot, world_id):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)
            env.update({
                "PYTHONPATH": repo,
                "HOROVOD_HOSTNAME": slot.hostname,
                "HOROVOD_LOCAL_RANK": str(slot.local_rank),
                "HOROVOD_ELASTIC": "1",
                "HOROVOD_ELASTIC_DRIVER_ADDR": "127.0.0.1",
                "HOROVOD_ELASTIC_DRIVER_PORT": str(driver.service_port),
                "HOROVOD_ELASTIC_DRIVER_KEY": driver.key.hex(),
                "HOROVOD_START_TIMEOUT": "30",
                "HOROVOD_FLIGHT_RECORDER_DIR": flight_dir,
            })
            env.update(plan.to_env())
            cmd = " ".join(shlex.quote(c) for c in [
                _sys.executable, worker, "--log-file", log_file,
                "--batches", "8", "--batch-sleep", "0.1"])
            return safe_shell_exec.execute(cmd, env=env)

        try:
            driver.start(_exec)
            ok = driver.join(timeout=240)
        finally:
            driver.stop()
            driver.shutdown_service()
            chaos_mod.reset()
        assert ok

        pm = _load_postmortem()
        report = pm.build_report(flight_dir)
        assert not report["corrupt"], report["corrupt"]
        # every rank of the crashed incarnation left a parseable dump:
        # the dead rank's chaos.crash black box + both survivors' reset
        # dumps
        assert len(report["ranks"]) == 3, report["ranks"]
        assert len(report["crashed_ranks"]) == 1, report["ranks"]
        dead = report["ranks"][report["crashed_ranks"][0]]
        assert dead["reason"] == "chaos.crash"
        assert dead["identity"]["hostname"] == "hostB"
        survivors = [r for k, r in report["ranks"].items()
                     if k not in report["crashed_ranks"]]
        assert len(survivors) == 2
        assert all(r["reason"] == "elastic.reset" for r in survivors)
        assert all(r["identity"]["hostname"] == "hostA"
                   for r in survivors)
        # the postmortem places the divergence: commits stop for the
        # dead rank at its crash batch while survivors got further
        assert report["last_common_step"] is not None
        assert dead["last_step"] <= 4
        assert report["divergence_step"] is not None
        assert report["crashed_ranks"][0] in report["diverged_ranks"]
        # the dead rank's trail ends in real events, not silence
        assert dead["events"] > 0


# ---------------------------------------------------------------------------
# Armed-forensics overhead budget (<1% of a representative step)


class TestForensicsOverhead:
    def test_armed_forensics_under_one_percent_of_step(self):
        """Flight recording + straggler phase accounting armed must cost
        <1% of the same representative 8-device-mesh step the registry
        budget is measured against (the acceptance gate; the heavier
        cross-rank detect() runs on the reporter interval, not per
        step)."""
        mesh = hvd.mesh()
        tx = hvd.DistributedOptimizer(__import__("optax").sgd(0.01))
        params = {f"w{i}": jnp.full((512, 512), 0.01) for i in range(4)}
        state = tx.init(params)

        def loss_fn(p, x):
            h = x
            for i in range(4):
                h = jnp.tanh(h @ p[f"w{i}"])
            return jnp.mean(h ** 2)

        def spmd(p, s, x):
            loss, grads = jax.value_and_grad(loss_fn)(p, x)
            updates, ns = tx.update(grads, s, p)
            import optax
            return optax.apply_updates(p, updates), ns, hvd.allreduce(loss)

        step = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(), P(hvd.HVD_AXES)),
            out_specs=(P(), P(), P())))
        x = jnp.ones((64, 512))
        params, state, loss = step(params, state, x)
        jax.block_until_ready(loss)
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            params, state, loss = step(params, state, x)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
        step_secs = float(np.median(times))

        fr = FlightRecorder(capacity=4096, snapshot_every=1024)
        det = StragglerDetector(MetricsRegistry(enabled=True),
                                world=8, rank=0)
        n = 300
        t0 = time.perf_counter()
        for i in range(n):
            # a generous per-step forensic load: 4 ring events, the
            # full phase vector, and the end-of-step publication
            for j in range(4):
                fr.record("FLIGHT:COLLECTIVE", tid="flight",
                          args={"name": f"op.{i}.{j}", "ms": 1.0})
            for ph in ("compute", "wire.ici", "wire.dcn", "wire.pod",
                       "pp_bubble", "ckpt"):
                det.record_phase(ph, 1.0)
            det.end_step(i)
        per_step = (time.perf_counter() - t0) / n
        assert per_step < 0.01 * step_secs, (
            f"armed forensics {per_step * 1e6:.1f}us vs step "
            f"{step_secs * 1e6:.1f}us "
            f"({100 * per_step / step_secs:.2f}% >= 1%)")
