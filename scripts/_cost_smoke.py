#!/usr/bin/env python
"""Cost-model smoke (CI brick for docs/cost-model.md), run by
scripts/cost_smoke.sh on the 8-device virtual CPU mesh:

1. calibrate the link classes with the microbenchmark sweep and prove
   the store round-trips (geometry-keyed JSON beside the autotune
   cache);
2. enumerate + price the legal plan space: the ranked shortlist must be
   nonempty and sorted by predicted step-wire milliseconds;
3. lower the top-priced candidate and assert it is BIT-identical to the
   same knobs threaded without the pricing machinery — the cost model
   ranks plans, it must never change what they compute.
"""

import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.ops import fusion  # noqa: E402
from horovod_tpu.plan import calibrate as hvd_cal  # noqa: E402
from horovod_tpu.plan import planner as hvd_planner  # noqa: E402


def main():
    assert len(jax.devices()) >= 8, "need 8 virtual CPU devices"
    hvd.init(devices=jax.devices()[:8], mesh_shape=(2, 4))
    mesh = hvd.mesh()

    # -- 1. calibrate + persistence round-trip -------------------------
    calib = hvd_cal.calibrate_links(sizes=(4096, 32768, 262144), reps=2)
    assert calib.links, "sweep fitted no link classes"
    for hop, lk in calib.links.items():
        assert lk.bandwidth_gbps > 0 and np.isfinite(lk.bandwidth_gbps), \
            f"{hop}: bad bandwidth {lk.bandwidth_gbps}"
        assert lk.latency_us >= 0, f"{hop}: negative latency"
        assert lk.quant_rate_gbps > 0, f"{hop}: bad quant rate"
    loaded = hvd_cal.load_calibration()
    assert loaded is not None, \
        f"stored calibration did not load back from " \
        f"{hvd_cal.calibration_path()}"
    assert loaded.geometry == calib.geometry
    assert set(loaded.links) == set(calib.links)
    model = hvd_cal.get_cost_model()
    assert model.source == "calibrated", model.source
    print(f"cost smoke: calibrated {sorted(calib.links)} on "
          f"{calib.geometry} -> "
          f"{ {h: round(lk.bandwidth_gbps, 2) for h, lk in calib.links.items()} } GB/s")

    # -- 2. shortlist: nonempty, ranked ascending ----------------------
    shortlist = hvd_planner.shortlist(
        8 * 1024 * 1024, quantized=True, tune_overlap=True, model=model)
    assert shortlist, "shortlist is empty"
    preds = [pp.predicted_ms for pp in shortlist]
    assert preds == sorted(preds), "shortlist is not ranked"
    assert all(p >= 0 for p in preds)
    top = shortlist[0]
    print(f"cost smoke: {len(shortlist)} priced plans, top "
          f"{top.plan.encode()} @ {top.predicted_ms:.4f} ms "
          f"(worst {preds[-1]:.4f} ms)")

    # -- 3. top candidate lowers bit-identically to the unpriced path --
    rs = np.random.RandomState(7)
    tree = {"w": jnp.asarray(rs.randn(8, 96, 41), jnp.float32),
            "b": jnp.asarray(rs.randn(8, 23), jnp.float32)}
    p = top.params

    def run(tuned_params=None, **knobs):
        def f(t):
            local = jax.tree.map(lambda v: v[0], t)
            return fusion.allreduce_pytree(
                local, op=hvd.Sum, tuned_params=tuned_params,
                quantized=True, **knobs)

        return hvd.shard_map(f, mesh=mesh, in_specs=P(hvd.HVD_AXES),
                             out_specs=P())(tree)

    out_priced = run(tuned_params=p)
    out_plain = run(
        threshold_bytes=p.fusion_threshold_bytes, block=p.quant_block,
        hierarchical=p.hierarchical_allreduce, overlap=p.overlap,
        num_comm_streams=p.num_comm_streams)
    for k in tree:
        np.testing.assert_array_equal(
            np.asarray(out_priced[k]), np.asarray(out_plain[k]),
            err_msg=f"top shortlist candidate diverges from the "
                    f"unpriced lowering on leaf {k!r}")
    print(f"cost smoke OK: top candidate "
          f"(thr={p.fusion_threshold_bytes >> 20}MiB block="
          f"{p.quant_block} streams={p.num_comm_streams}) lowers "
          f"bit-identically to the unpriced plan")


if __name__ == "__main__":
    main()
