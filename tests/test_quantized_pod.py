"""The quantized pod hop (docs/wire-plan.md): the pod level of a 3-level
tree plan spelled as the blockwise-int8 rs+ag pair.

* **validation and derivation** — int8 on a psum leg is refused, and
  ``quantized_pod`` (argument or ``HOROVOD_QUANTIZED_POD``) derives the
  ``pod.rs[int8] > pod.ag[int8]`` pair inside the tree ladder;
* **numerics and accounting** on the 2x2x2 mesh — int8 error on the pod
  links, bounded; the exact psum where the shard does not split;
* **golden text** — the ``describe_plan`` table of the plan, pinned
  literally;
* the per-level ``HOROVOD_BENCH_POD_GBPS`` bandwidth model the pod hop
  is charged at.
"""

import jax
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.common import basics
from horovod_tpu.plan import (DCN, ICI, INT8, POD, Leg, PlanError,
                              WirePlan, describe_plan)
from horovod_tpu.plan.accounting import bench_gbps, _modeled_wire_ms

N = 8


@pytest.fixture(scope="module", autouse=True)
def _mesh_2x4():
    hvd.shutdown()
    hvd.init(mesh_shape=(2, 4))
    yield
    hvd.shutdown()
    hvd.init()


GOLDEN_QUANTIZED_POD_2x2x2 = """\
wire plan  mesh=2x2x2  payload=1048576B (itemsize 4)
knobs: quantized=off block=256 zero_stage=0 overlap=off hierarchical=on streams=1 fusion_threshold=67108864 quantized_pod=on
collective       leg level primitive      wire       ef  backend stream    bytes/dev  model ms  pred ms
allreduce          1 ici   reduce_scatter payload    -   xla          0       524288    0.0052   0.0062
allreduce          2 dcn   psum           payload    -   xla          0       524288    0.0210   0.0460
allreduce          3 pod   reduce_scatter int8/256   -   xla          0        66560    0.0027   0.0329
allreduce          4 pod   all_gather     int8/256   -   xla          0       133120    0.0053   0.0408
allreduce          5 ici   all_gather     payload    -   xla          0      1048576    0.0105   0.0115
totals: ici=1572864 dcn=524288 pod=199680 dcn_fp_equiv=524288 dcn_reduction=1.00x pod_fp_equiv=786432 pod_reduction=3.94x
predicted: 0.1374 ms step wire = bytes 0.0447 + latency 0.0770 + quant 0.0157 - hidden 0.0000 (modeled 0.0447 ms, 1 bucket) [cost model: static]
encoding: allreduce:ici.reduce_scatter[payload]>dcn.psum[payload]>pod.reduce_scatter[int8/256]>pod.all_gather[int8/256]>ici.all_gather[payload]|s1|sync"""


class TestGoldenTables:
    def test_quantized_pod_table(self):
        sp = describe_plan(hierarchical=True, quantized_pod=True,
                           mesh_shape=(2, 2, 2),
                           fusion_threshold_bytes=64 * 1024 * 1024,
                           quant_block=256)
        assert sp.table(payload_bytes=1 << 20) == \
            GOLDEN_QUANTIZED_POD_2x2x2


# ---------------------------------------------------------------------------
# The quantized pod hop (3-level tree plans).
# ---------------------------------------------------------------------------


class TestQuantizedPod:
    @pytest.fixture()
    def mesh_2x2x2(self):
        grid = np.array(jax.devices()[:N]).reshape(2, 2, 2)
        return Mesh(grid, basics.ALL_AXES)

    def test_validation_rejects_int8_psum(self):
        p = WirePlan("allreduce", (
            Leg(ICI, "reduce_scatter"), Leg(POD, "psum", INT8),
            Leg(ICI, "all_gather")))
        with pytest.raises(PlanError, match="not closed under addition"):
            p.validate()

    def test_planner_knob_builds_pod_rs_ag_pair(self):
        sp = describe_plan(hierarchical=True, quantized_pod=True,
                           mesh_shape=(2, 2, 2))
        assert sp.quantized_pod
        legs = sp.gradient.legs
        assert [(l.level, l.primitive) for l in legs] == [
            (ICI, "reduce_scatter"), (DCN, "psum"),
            (POD, "reduce_scatter"), (POD, "all_gather"),
            (ICI, "all_gather")]
        assert legs[2].wire_dtype == INT8 and legs[3].wire_dtype == INT8
        assert not sp.gradient.is_dcn_quantized  # routes via the tree

    def test_smoke_2x2x2_numerics_and_accounting(self, mesh_2x2x2):
        # Per-rank payload dim 0 divisible by local_size=2 AND the
        # post-ICI shard by pod_size=2 → the quantized pod pair engages.
        rng = np.random.RandomState(0)
        x = rng.randn(8, 64).astype(np.float32)
        spec = P(basics.ALL_AXES)
        sp = describe_plan(hierarchical=True, quantized_pod=True,
                           mesh_shape=(2, 2, 2))

        def fn(xs):
            return hvd.allreduce(xs[0], op=hvd.Sum, plan=sp.gradient)

        out = hvd.shard_map(fn, mesh=mesh_2x2x2, in_specs=(spec,),
                            out_specs=P())(x)
        ref = x.sum(axis=0)
        err = np.abs(np.asarray(out) - ref).max()
        # Quantization error: bounded by quanta of the partial sums the
        # pod hop carries — and NONZERO, proving int8 actually rode the
        # pod links (the exact psum would be ~1e-6).
        bound = 8.0 * np.abs(x).max() / 127.0
        assert 1e-5 < err <= bound, err
        with hvd.record_wire_stats() as ws:
            jax.jit(hvd.shard_map(fn, mesh=mesh_2x2x2, in_specs=(spec,),
                                  out_specs=P())).lower(x)
        assert ws.pod_bytes > 0 and ws.pod_bytes_fp > 0
        assert ws.dcn_bytes > 0 and ws.ici_bytes > 0

    def test_non_divisible_pod_shard_falls_back_exact(self, mesh_2x2x2):
        x = np.random.RandomState(1).randn(8, 7).astype(np.float32)
        spec = P(basics.ALL_AXES)
        sp = describe_plan(hierarchical=True, quantized_pod=True,
                           mesh_shape=(2, 2, 2))
        got = hvd.shard_map(
            lambda xs: hvd.allreduce(xs, op=hvd.Sum, plan=sp.gradient),
            mesh=mesh_2x2x2, in_specs=(spec,), out_specs=P())(x)
        ref = hvd.shard_map(
            lambda xs: lax.psum(xs, basics.ALL_AXES),
            mesh=mesh_2x2x2, in_specs=(spec,), out_specs=P())(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_env_knob_route(self, mesh_2x2x2, monkeypatch):
        monkeypatch.setenv("HOROVOD_QUANTIZED_POD", "1")
        hvd.shutdown()
        hvd.init()
        try:
            sp = describe_plan(hierarchical=True, mesh_shape=(2, 2, 2))
            assert sp.quantized_pod
            assert "pod.reduce_scatter[int8/256]" in sp.gradient.encode()
        finally:
            hvd.shutdown()
            hvd.init(mesh_shape=(2, 4))


# ---------------------------------------------------------------------------
# Per-level modeled bandwidths (HOROVOD_BENCH_POD_GBPS).
# ---------------------------------------------------------------------------


class TestPodBandwidthModel:
    def test_pod_defaults_to_dcn(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_BENCH_POD_GBPS", raising=False)
        monkeypatch.setenv("HOROVOD_BENCH_DCN_GBPS", "40")
        ici, dcn, pod = bench_gbps()
        assert dcn == 40.0 and pod == 40.0

    def test_pod_knob_overrides(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_BENCH_DCN_GBPS", "25")
        monkeypatch.setenv("HOROVOD_BENCH_POD_GBPS", "5")
        ici, dcn, pod = bench_gbps()
        assert pod == 5.0 and dcn == 25.0
        # modeled time: the pod term rides its own bandwidth
        ms = _modeled_wire_ms(0.0, 0.0, 5e9)
        assert ms == pytest.approx(1000.0)
        assert _modeled_wire_ms(0.0, 25e9, 0.0) == pytest.approx(1000.0)

    def test_wire_stats_pod_class_separate(self, monkeypatch):
        # flat psum over a 2x2x2 mesh charges the cross-pod hop to the
        # pod class, not dcn (the uniform-DCN assumption is gone).
        grid = np.array(jax.devices()[:N]).reshape(2, 2, 2)
        mesh = Mesh(grid, basics.ALL_AXES)
        x = np.random.RandomState(0).randn(8, 64).astype(np.float32)
        spec = P(basics.ALL_AXES)
        with hvd.record_wire_stats() as ws:
            jax.jit(hvd.shard_map(
                lambda xs: hvd.allreduce(xs, op=hvd.Sum),
                mesh=mesh, in_specs=(spec,), out_specs=P())).lower(x)
        assert ws.pod_bytes > 0
        assert ws.pod_bytes < ws.dcn_bytes < ws.ici_bytes
