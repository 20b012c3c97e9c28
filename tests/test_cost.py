"""Cost-model-driven planner tests (docs/cost-model.md).

Tiers mirror the subsystem: the per-link CostModel and its env
resolution, analytic plan/step pricing (alpha-beta + quantize + overlap
terms over the exact trace-time byte formulas), the enumerate → price →
shortlist pipeline, the calibration sweep's alpha-beta fit and its
persistence contract (geometry-keyed store beside the autotune cache;
corrupted/missing/mismatched entries fall back to the static defaults
with a warning, never an abort), and the predicted-vs-measured drift
contract against the live trace-time accounting."""

import dataclasses
import json
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.common import basics
from horovod_tpu.ops import fusion
from horovod_tpu.plan import (
    CostModel,
    LinkClass,
    StepPlan,
    calibrate as hvd_calibrate,
    cost as hvd_cost,
    describe_plan,
    enumerate_tuned,
    modeled_wire_ms,
    price_plan,
    price_step,
    quantized_allreduce_plan,
    record_wire_stats,
    shortlist,
    tree_allreduce_plan,
    flat_plan,
)

MIB = 1024 * 1024


def mesh_2x4():
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), hvd.HVD_AXES)


class TestCostModel:
    def test_static_defaults_match_bench_gbps(self):
        m = CostModel.from_env()
        assert m.source == "static"
        assert m.ici.bandwidth_gbps == 100.0
        assert m.dcn.bandwidth_gbps == 25.0
        assert m.pod.bandwidth_gbps == 25.0  # pod defaults to DCN
        assert m.ici.latency_us == 1.0
        assert m.dcn.latency_us == 25.0
        assert m.pod.latency_us == 25.0
        assert m.dcn.quant_rate_gbps == 50.0

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_BENCH_DCN_GBPS", "10")
        monkeypatch.setenv("HOROVOD_BENCH_DCN_LAT_US", "100")
        monkeypatch.setenv("HOROVOD_BENCH_QUANT_GBPS", "5")
        m = CostModel.from_env()
        assert m.dcn.bandwidth_gbps == 10.0
        assert m.dcn.latency_us == 100.0
        assert m.ici.quant_rate_gbps == 5.0
        # pod inherits the overridden DCN values when unset
        assert m.pod.bandwidth_gbps == 10.0
        assert m.pod.latency_us == 100.0

    def test_link_lookup_rejects_unknown_hop(self):
        m = CostModel.from_env()
        assert m.link("dcn") is m.dcn
        with pytest.raises(ValueError, match="unknown link class"):
            m.link("nvlink")


class TestPricePlan:
    N = (1 << 20) // 4  # 1 MiB fp32

    def test_modeled_is_bytes_at_bench_bandwidth(self):
        pc = price_plan(flat_plan("allreduce"), self.N, 4, (2, 4))
        # flat psum over 2x4: ici 2n(3/4), dcn 2(n/4)(1/2) — at
        # 100/25 GB/s.
        n_bytes = self.N * 4
        want = (2 * n_bytes * 3 / 4 / 100e9
                + 2 * (n_bytes / 4) * 1 / 2 / 25e9) * 1e3
        assert pc.modeled_ms == pytest.approx(want, rel=1e-9)
        # Static model: predicted wire == modeled wire (drift-free by
        # construction; only latency/quant terms are added on top).
        assert pc.wire_ms == pytest.approx(pc.modeled_ms, rel=1e-9)

    def test_alpha_counts_ring_hops(self):
        pc = price_plan(tree_allreduce_plan(), self.N, 4, (2, 4))
        # ici legs: (4-1) hops at 1 us each; dcn psum: (2-1) at 25 us.
        ici_alpha = sum(l.alpha_ms for l in pc.legs if l.hop == "ici")
        dcn_alpha = sum(l.alpha_ms for l in pc.legs if l.hop == "dcn")
        assert ici_alpha == pytest.approx(2 * 3 * 1.0 / 1e3)
        assert dcn_alpha == pytest.approx(1 * 25.0 / 1e3)

    def test_quant_term_prices_fp_equivalent_payload(self):
        q = price_plan(quantized_allreduce_plan(block=256), self.N, 4,
                       (2, 4))
        assert q.quant_ms > 0
        # fp-equivalent payload of the two int8 legs at the 50 GB/s
        # quant rate: rs fp = sn(nc-1)/nc, ag fp = 2 sn(nc-1)/nc.
        sn = self.N // 4
        fp = (sn * 0.5 + 2 * sn * 0.5) * 4
        assert q.quant_ms == pytest.approx(fp / 50e9 * 1e3, rel=1e-6)

    def test_quantized_wire_cheaper_on_slow_dcn(self):
        # The int8 wire must price below the exact wire once the DCN
        # link is slow enough — EQuARX's premise as a model consequence.
        slow_dcn = CostModel(
            ici=LinkClass(100.0, 1.0, 50.0),
            dcn=LinkClass(2.0, 25.0, 50.0),
            pod=LinkClass(2.0, 25.0, 50.0))
        exact = price_plan(tree_allreduce_plan(), self.N, 4, (2, 4),
                           slow_dcn)
        quant = price_plan(quantized_allreduce_plan(block=256), self.N,
                           4, (2, 4), slow_dcn)
        assert quant.total_ms < exact.total_ms

    def test_calibrated_bandwidth_changes_wire_not_modeled(self):
        fast = CostModel(
            ici=LinkClass(200.0, 1.0, 50.0),
            dcn=LinkClass(50.0, 25.0, 50.0),
            pod=LinkClass(50.0, 25.0, 50.0), source="calibrated")
        pc = price_plan(flat_plan("allreduce"), self.N, 4, (2, 4), fast)
        # Calibrated wire halves; the modeled (static-bandwidth) column
        # stays the WireStats-comparable figure.
        assert pc.wire_ms == pytest.approx(pc.modeled_ms / 2, rel=1e-9)


class TestPriceStep:
    def _sp(self, **kw):
        kw.setdefault("quantized", False)
        kw.setdefault("mesh_shape", (2, 4))
        kw.setdefault("fusion_threshold_bytes", 4 * MIB)
        kw.setdefault("quant_block", 256)
        return describe_plan(**kw)

    def test_buckets_multiply_alpha_not_bytes(self):
        one = price_step(self._sp(fusion_threshold_bytes=64 * MIB),
                         32 * MIB)
        many = price_step(self._sp(fusion_threshold_bytes=4 * MIB),
                          32 * MIB)
        assert one.buckets == 1 and many.buckets == 8
        assert many.wire_ms == pytest.approx(one.wire_ms, rel=1e-9)
        assert many.alpha_ms == pytest.approx(one.alpha_ms * 8, rel=1e-9)

    def test_overlap_hides_all_but_the_tail_bucket(self):
        sync = price_step(self._sp(fusion_threshold_bytes=4 * MIB),
                          32 * MIB)
        ovl = price_step(self._sp(fusion_threshold_bytes=4 * MIB,
                                  overlap=True), 32 * MIB)
        assert sync.hidden_ms == 0.0
        assert ovl.hidden_ms == pytest.approx(
            ovl.wire_ms * (1 - 1 / 8), rel=1e-9)
        assert ovl.predicted_ms < sync.predicted_ms

    def test_compute_budget_caps_the_overlap_credit(self):
        ovl = price_step(self._sp(fusion_threshold_bytes=4 * MIB,
                                  overlap=True), 32 * MIB,
                         compute_ms=0.01)
        assert ovl.hidden_ms == pytest.approx(0.01)

    def test_streams_amortize_flight_alphas(self):
        s1 = price_step(self._sp(fusion_threshold_bytes=4 * MIB,
                                 overlap=True, num_comm_streams=1),
                        32 * MIB)
        s4 = price_step(self._sp(fusion_threshold_bytes=4 * MIB,
                                 overlap=True, num_comm_streams=4),
                        32 * MIB)
        assert s1.flights == 8 and s4.flights == 2
        assert s4.alpha_ms == pytest.approx(s1.alpha_ms / 4, rel=1e-9)

    def test_zero_step_prices_both_halves(self):
        sp = self._sp(zero_stage=2)
        sc = price_step(sp, 8 * MIB)
        assert len(sc.plan_costs) == 2  # rs + ag
        assert sc.predicted_ms > 0


class TestShortlist:
    def test_every_candidate_validates_and_is_ranked(self):
        rows = shortlist(16 * MIB, mesh_shape=(2, 4), quantized=True,
                         tune_overlap=True, tune_zero=True)
        assert rows
        preds = [r.predicted_ms for r in rows]
        assert preds == sorted(preds)
        for r in rows:
            assert isinstance(r.plan, StepPlan)
            for plan in r.plan.plans:
                plan.validate()  # must already be legal

    def test_derived_wire_dedup(self):
        rows = shortlist(16 * MIB, mesh_shape=(2, 4), quantized=True,
                         tune_overlap=True)
        keys = [(r.plan.encode(), r.params.fusion_threshold_bytes)
                for r in rows]
        assert len(keys) == len(set(keys))

    def test_gates_pin_dimensions(self):
        rows = shortlist(16 * MIB, mesh_shape=(2, 4), quantized=False)
        assert all(r.params.zero_stage == 0 for r in rows)
        assert all(not r.params.overlap for r in rows)
        zrows = shortlist(16 * MIB, mesh_shape=(2, 4), quantized=False,
                          tune_zero=True)
        assert {r.params.zero_stage for r in zrows} == {0, 1, 2}

    def test_k_truncates_the_head(self):
        full = shortlist(16 * MIB, mesh_shape=(2, 4), quantized=True)
        top = shortlist(16 * MIB, mesh_shape=(2, 4), quantized=True, k=3)
        assert len(top) == 3
        assert [r.plan.encode() for r in top] == \
            [r.plan.encode() for r in full[:3]]

    def test_as_dict_round_trips_to_json(self):
        rows = shortlist(8 * MIB, mesh_shape=(2, 4), quantized=True, k=2)
        blob = json.dumps([r.as_dict() for r in rows])
        back = json.loads(blob)
        assert back[0]["plan"] == rows[0].plan.encode()
        assert back[0]["predicted_ms"] == pytest.approx(
            rows[0].predicted_ms, abs=1e-6)

    def test_enumerate_respects_initial_for_pinned_dims(self):
        from horovod_tpu.autotune import TunedParams

        init = TunedParams(fusion_threshold_bytes=2 * MIB,
                           quant_block=192)
        cands = enumerate_tuned(quantized=True, initial=init)
        assert any(p.fusion_threshold_bytes == 2 * MIB for p in cands)
        assert any(p.quant_block == 192 for p in cands)


class TestAlphaBetaFit:
    def test_recovers_synthetic_link(self):
        # t = 50us + bytes / 40 GB/s
        pts = [(b, 50e-6 + b / 40e9)
               for b in (16e3, 128e3, 1e6, 4e6)]
        bw, lat = hvd_calibrate.alpha_beta_fit(
            pts, fallback_gbps=1.0, fallback_lat_us=0.0)
        assert bw == pytest.approx(40.0, rel=1e-6)
        assert lat == pytest.approx(50.0, rel=1e-6)

    def test_degenerate_slope_falls_back_to_static(self):
        pts = [(16e3, 1e-3), (1e6, 1e-3)]  # flat: timer noise
        bw, lat = hvd_calibrate.alpha_beta_fit(
            pts, fallback_gbps=25.0, fallback_lat_us=7.0)
        assert (bw, lat) == (25.0, 7.0)
        assert hvd_calibrate.alpha_beta_fit(
            [(1e6, 1e-3)], fallback_gbps=3.0,
            fallback_lat_us=2.0) == (3.0, 2.0)

    def test_negative_intercept_clamps_to_zero(self):
        pts = [(b, b / 40e9 - 1e-6) for b in (1e6, 2e6, 4e6)]
        _, lat = hvd_calibrate.alpha_beta_fit(
            pts, fallback_gbps=1.0, fallback_lat_us=9.0)
        assert lat == 0.0


class TestCalibrationPersistence:
    def _calib(self, geometry=None):
        return hvd_calibrate.Calibration(
            geometry=geometry or basics.mesh_geometry(),
            links={"ici": LinkClass(123.0, 2.5, 44.0),
                   "dcn": LinkClass(20.0, 30.0, 44.0)},
            points={"ici": [(16e3, 1e-4), (1e6, 2e-4)]},
            created_unix=1.0)

    def test_json_round_trip(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_CALIBRATION_CACHE",
                           str(tmp_path / "cal.json"))
        calib = self._calib()
        hvd_calibrate.store_calibration(calib)
        loaded = hvd_calibrate.load_calibration()
        assert loaded is not None
        assert loaded.geometry == calib.geometry
        assert loaded.links == calib.links
        assert loaded.points["ici"] == calib.points["ici"]
        model = hvd_calibrate.get_cost_model()
        assert model.source == "calibrated"
        assert model.ici.bandwidth_gbps == 123.0
        # Levels the sweep did not fit keep the static defaults.
        assert model.pod.bandwidth_gbps == \
            CostModel.from_env().pod.bandwidth_gbps

    def test_geometry_mismatch_forces_resweep(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("HOROVOD_CALIBRATION_CACHE",
                           str(tmp_path / "cal.json"))
        # A sweep from a DIFFERENT geometry is stored, but never
        # trusted for this one: load misses, the model stays static.
        hvd_calibrate.store_calibration(
            self._calib(geometry="mesh64x4|world256|tpu-v5e"))
        assert hvd_calibrate.load_calibration() is None
        assert hvd_calibrate.get_cost_model().source == "static"
        # The mismatched-geometry entry itself is still on disk intact.
        disk = json.load(open(str(tmp_path / "cal.json")))
        assert any("mesh64x4" in k for k in disk)

    def test_corrupted_file_warns_and_falls_back(self, tmp_path,
                                                 monkeypatch, caplog):
        path = tmp_path / "cal.json"
        path.write_text("{ not json !!!")
        monkeypatch.setenv("HOROVOD_CALIBRATION_CACHE", str(path))
        with caplog.at_level(logging.WARNING,
                             logger="horovod_tpu.plan"):
            assert hvd_calibrate.load_calibration() is None
            model = hvd_calibrate.get_cost_model()
        assert model.source == "static"
        assert model.dcn.bandwidth_gbps == 25.0  # HOROVOD_BENCH default
        assert any("unreadable" in r.message for r in caplog.records)

    def test_malformed_entry_warns_and_falls_back(self, tmp_path,
                                                  monkeypatch, caplog):
        path = tmp_path / "cal.json"
        key = hvd_calibrate.geometry_key()
        path.write_text(json.dumps({key: {"geometry": "x"}}))  # no links
        monkeypatch.setenv("HOROVOD_CALIBRATION_CACHE", str(path))
        with caplog.at_level(logging.WARNING,
                             logger="horovod_tpu.plan"):
            assert hvd_calibrate.load_calibration() is None
        assert hvd_calibrate.get_cost_model().source == "static"
        assert any("malformed" in r.message for r in caplog.records)

    def test_missing_file_is_silent_static(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_CALIBRATION_CACHE",
                           str(tmp_path / "nope" / "cal.json"))
        assert hvd_calibrate.load_calibration() is None
        assert hvd_calibrate.get_cost_model().source == "static"

    def test_default_path_sits_beside_the_autotune_cache(
            self, tmp_path, monkeypatch):
        monkeypatch.delenv("HOROVOD_CALIBRATION_CACHE", raising=False)
        monkeypatch.setenv("HOROVOD_AUTOTUNE_CACHE",
                           str(tmp_path / "sub" / "kernel.json"))
        assert hvd_calibrate.calibration_path() == \
            str(tmp_path / "sub" / "link_calibration.json")


class TestCalibrationSweep:
    def test_live_sweep_fits_and_persists(self, tmp_path, monkeypatch):
        """Real microbenchmark on the live test mesh: fits positive
        finite triples for every level the mesh has, persists, and
        resolves as the calibrated model."""
        monkeypatch.setenv("HOROVOD_CALIBRATION_CACHE",
                           str(tmp_path / "cal.json"))
        calib = hvd_calibrate.calibrate_links(sizes=(4096, 65536),
                                              reps=1)
        assert calib.geometry == basics.mesh_geometry()
        assert "ici" in calib.links  # the local axis always exists
        for lk in calib.links.values():
            assert lk.bandwidth_gbps > 0
            assert math.isfinite(lk.bandwidth_gbps)
            assert lk.latency_us >= 0
            assert lk.quant_rate_gbps > 0
        assert "quant" in calib.points
        assert hvd_calibrate.get_cost_model().source == "calibrated"

    def test_sweep_requires_init(self, monkeypatch):
        monkeypatch.setattr(basics._state, "initialized", False)
        with pytest.raises(RuntimeError, match="init"):
            hvd_calibrate.calibrate_links()


class TestMeshGeometry:
    def test_explicit_shape(self):
        geo = basics.mesh_geometry(mesh_shape=(2, 4))
        assert geo.startswith("mesh2x4|world8|")

    def test_three_level_shape(self):
        geo = basics.mesh_geometry(mesh_shape=(2, 2, 2))
        assert geo.startswith("mesh2x2x2|world8|")

    def test_live_mesh_matches_devices_shape(self):
        geo = basics.mesh_geometry()
        shp = hvd.mesh().devices.shape
        assert geo.startswith(
            "mesh" + "x".join(str(v) for v in shp) + "|world8|")


class TestDriftContract:
    def test_predicted_matches_traced_accounting(self):
        """The drift gate's core promise: the planner's byte model and
        the compiler's trace-time accounting are the same formulas. A
        real quantized allreduce traced on the 2x4 mesh must account
        wire bytes whose modeled-ms matches the prediction within a few
        percent (bucket padding is the only slack)."""
        n = 256 * 1024  # elements, divisible by world and block
        tree = {"w": jnp.zeros((8, n), jnp.float32)}
        payload_bytes = n * 4

        with record_wire_stats() as ws:
            jax.jit(hvd.shard_map(
                lambda t: fusion.allreduce_pytree(
                    jax.tree.map(lambda v: v[0], t), op=hvd.Sum,
                    quantized=True),
                mesh=mesh_2x4(), in_specs=(P(hvd.HVD_AXES),),
                out_specs=P())).lower(tree)
        measured = modeled_wire_ms(ws.ici_bytes, ws.dcn_bytes,
                                   ws.pod_bytes)
        sp = describe_plan(quantized=True, mesh_shape=(2, 4),
                           quant_block=256,
                           fusion_threshold_bytes=64 * MIB)
        predicted = price_step(sp, payload_bytes).wire_ms
        assert measured > 0
        assert predicted == pytest.approx(measured, rel=0.03)

    def test_static_model_is_drift_free_by_construction(self):
        sp = describe_plan(quantized=True, mesh_shape=(2, 4),
                           quant_block=256,
                           fusion_threshold_bytes=64 * MIB)
        sc = price_step(sp, 4 * MIB)
        assert sc.wire_ms == pytest.approx(sc.modeled_ms, rel=1e-9)
        assert sc.as_dict()["model"] == "static"


class TestTablePricing:
    def test_table_carries_model_and_pred_columns(self):
        sp = describe_plan(quantized=True, mesh_shape=(2, 4),
                           fusion_threshold_bytes=64 * MIB,
                           quant_block=256)
        text = sp.table(payload_bytes=1 << 20)
        assert "model ms" in text and "pred ms" in text
        assert "predicted:" in text
        assert "[cost model: static]" in text

    def test_table_prices_with_a_calibrated_model(self):
        sp = describe_plan(quantized=False, mesh_shape=(2, 4),
                           fusion_threshold_bytes=64 * MIB,
                           quant_block=256)
        fast = CostModel(
            ici=LinkClass(200.0, 0.0, 50.0),
            dcn=LinkClass(50.0, 0.0, 50.0),
            pod=LinkClass(50.0, 0.0, 50.0),
            source="calibrated", geometry="mesh2x4|world8|test")
        text = sp.table(payload_bytes=1 << 20, model=fast)
        assert "[cost model: calibrated]" in text
