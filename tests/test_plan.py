"""Wire-plan IR tests (docs/wire-plan.md).

Four tiers:

* **validation** — illegal leg compositions fail loudly with actionable
  messages (ISSUE 9 satellite: plan validation units);
* **golden text** — ``hvd.describe_plan(...).table()`` is pinned as
  literal text, so any plan regression shows up as a readable diff;
* **equivalence matrix** — the plan compiler's output is bit-identical
  to the pre-refactor hand-composed paths for every knob combination in
  {quantized, zero_stage 0/2/3, overlap, hierarchical} on the 8-device
  2x4 mesh: the wire-level references below are literal copies of the
  deleted bespoke bodies (renamed), and the optimizer-level matrix
  re-asserts the cross-knob invariants (overlap-on ≡ overlap-off,
  plan= ≡ booleans) the old paths guaranteed;
* **3-level smoke** — a plan-compiled allreduce on an emulated 2x2x2
  ``(pod, cross, local)`` mesh, plus the ``--mesh-shape CxLxP`` parsing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.common import basics
from horovod_tpu.ops import collective_ops as C
from horovod_tpu.ops import compression as Z
from horovod_tpu.plan import (DCN, FLAT, ICI, INT8, POD, Leg, PlanError,
                              WirePlan, decode_tuned, describe_plan,
                              encode_tuned, planner)

N = 8


@pytest.fixture(scope="module", autouse=True)
def _mesh_2x4():
    """Emulated 2-host x 4-chip mesh (the DCN hop the quantized legs
    compress); restore the default mesh for later modules."""
    hvd.shutdown()
    hvd.init(mesh_shape=(2, 4))
    yield
    hvd.shutdown()
    hvd.init()


def mesh_2x4() -> Mesh:
    return hvd.mesh()


# ---------------------------------------------------------------------------
# Validation: illegal compositions fail loudly with actionable messages.
# ---------------------------------------------------------------------------


class TestValidation:
    def test_int8_on_ici_hop_rejected(self):
        p = WirePlan("allreduce", (Leg(ICI, "reduce_scatter", INT8),
                                   Leg(ICI, "all_gather")))
        with pytest.raises(PlanError, match="non-DCN hop"):
            p.validate()

    def test_reduce_leg_after_gather_rejected(self):
        p = WirePlan("allreduce", (
            Leg(ICI, "reduce_scatter"), Leg(ICI, "all_gather"),
            Leg(DCN, "psum")))
        with pytest.raises(PlanError, match="illegal leg order"):
            p.validate()

    def test_unbalanced_allreduce_rejected(self):
        p = WirePlan("allreduce", (Leg(ICI, "reduce_scatter"),
                                   Leg(DCN, "psum")))
        with pytest.raises(PlanError, match="re-gathered in mirror order"):
            p.validate()

    def test_bad_stream_count_rejected(self):
        p = WirePlan("allreduce", (Leg(FLAT, "psum"),), streams=3)
        with pytest.raises(PlanError, match="power of two in 1..4"):
            p.validate()

    def test_unknown_primitive_and_level_rejected(self):
        with pytest.raises(PlanError, match="unknown primitive"):
            WirePlan("allreduce", (Leg(ICI, "ring_exchange"),)).validate()
        with pytest.raises(PlanError, match="unknown level"):
            WirePlan("allreduce", (Leg("nvlink", "psum"),)).validate()
        with pytest.raises(PlanError, match="unknown collective"):
            WirePlan("gossip", (Leg(FLAT, "psum"),)).validate()

    def test_ef_on_exact_ici_leg_rejected(self):
        p = WirePlan("allreduce", (
            Leg(ICI, "reduce_scatter", error_feedback=True),
            Leg(ICI, "all_gather")))
        with pytest.raises(PlanError, match="error-feedback slot"):
            p.validate()

    def test_gather_leg_in_reduce_scatter_plan_rejected(self):
        p = WirePlan("reduce_scatter", (Leg(ICI, "reduce_scatter"),
                                        Leg(ICI, "all_gather")))
        with pytest.raises(PlanError, match="belongs to the all_gather"):
            p.validate()

    def test_flat_leg_cannot_compose(self):
        p = WirePlan("allreduce", (Leg(FLAT, "psum"),
                                   Leg(ICI, "all_gather")))
        with pytest.raises(PlanError, match="WHOLE plan"):
            p.validate()

    def test_valid_plans_validate(self):
        planner.flat_plan("allreduce")
        planner.tree_allreduce_plan()
        planner.tree_allreduce_plan(pod=True)
        planner.quantized_allreduce_plan(block=256, error_feedback=True)
        planner.zero_reduce_scatter_plan(quantized=True, block=128)
        planner.zero_all_gather_plan(quantized=True, block=128)


# ---------------------------------------------------------------------------
# Planner: knob combinations → plan structure; autotune plan encoding.
# ---------------------------------------------------------------------------


class TestPlanner:
    def test_knob_matrix_maps_to_expected_structures(self):
        levels = (ICI, DCN)
        flat = planner.derive_allreduce(levels=levels, quantized=False,
                                        hierarchical=False)
        assert flat.is_flat and not flat.is_quantized
        tree = planner.derive_allreduce(levels=levels, quantized=False,
                                        hierarchical=True)
        assert tree.levels == (ICI, DCN, ICI) and not tree.is_quantized
        quant = planner.derive_allreduce(levels=levels, quantized=True,
                                         hierarchical=False)
        assert quant.levels == (ICI, DCN, DCN, ICI)
        assert [l.wire_dtype for l in quant.legs] == [
            "payload", INT8, INT8, "payload"]
        # quantized wins over hierarchical (the pre-refactor precedence)
        both = planner.derive_allreduce(levels=levels, quantized=True,
                                        hierarchical=True)
        assert both == quant

    def test_custom_axes_always_flat(self):
        assert planner.derive_allreduce(
            levels=planner.levels_of(("tp",)), quantized=True,
            hierarchical=True).is_flat

    def test_zero_wire_is_the_split_allreduce(self):
        rs = planner.derive_reduce_scatter(levels=(ICI, DCN),
                                           quantized=True, block=256)
        ag = planner.derive_all_gather(levels=(ICI, DCN), quantized=True,
                                       block=256)
        q = planner.quantized_allreduce_plan(block=256)
        # rs legs == the reduce half, ag legs == the gather half.
        assert [(l.level, l.primitive) for l in rs.legs] == \
            [(l.level, l.primitive) for l in q.legs[:2]]
        assert [(l.level, l.primitive) for l in ag.legs] == \
            [(l.level, l.primitive) for l in q.legs[2:]]

    def test_describe_plan_three_level_tree(self):
        sp = describe_plan(hierarchical=True, mesh_shape=(2, 2, 2))
        assert sp.gradient.levels == (ICI, DCN, POD, ICI)
        sp0 = describe_plan(mesh_shape=(2, 2, 2))
        assert sp0.gradient.is_flat

    def test_encode_decode_round_trip(self):
        from horovod_tpu.autotune import TunedParams

        for p, quant in [
            (TunedParams(), False),
            (TunedParams(hierarchical_allreduce=True), False),
            (TunedParams(zero_stage=2, overlap=True,
                         num_comm_streams=4), True),
            (TunedParams(quant_block=128, overlap=True,
                         num_comm_streams=2), True),
        ]:
            enc = encode_tuned(p, quantized=quant)
            d = decode_tuned(enc)
            assert d["zero_stage"] == p.zero_stage
            assert d["overlap"] == p.overlap
            assert d["quantized"] == quant
            if quant:
                assert d["quant_block"] == p.quant_block
            if p.overlap:
                assert d["num_comm_streams"] == p.num_comm_streams

    def test_encoding_collapses_dead_knobs(self):
        from horovod_tpu.autotune import TunedParams

        # hierarchical is dead under the ZeRO rs+ag split; streams are
        # dead with overlap off — same wire, same encoding, ONE trial.
        a = encode_tuned(TunedParams(zero_stage=2,
                                     hierarchical_allreduce=True))
        b = encode_tuned(TunedParams(zero_stage=2))
        assert a == b
        c = encode_tuned(TunedParams(num_comm_streams=4))
        d = encode_tuned(TunedParams(num_comm_streams=1))
        assert c == d

    def test_decode_rejects_garbage(self):
        with pytest.raises(PlanError, match="unparseable plan encoding"):
            decode_tuned("ar.zigzag|fp|s1|sync")


# ---------------------------------------------------------------------------
# Golden text: the --dump-plan / describe_plan table, pinned literally.
# ---------------------------------------------------------------------------

GOLDEN_QUANTIZED_2x4 = """\
wire plan  mesh=2x4  payload=1048576B (itemsize 4)
knobs: quantized=on block=256 zero_stage=0 overlap=off hierarchical=off streams=1 fusion_threshold=67108864 quantized_pod=off
collective       leg level primitive      wire       ef  backend stream    bytes/dev  model ms  pred ms
allreduce          1 ici   reduce_scatter payload    -   xla          0       786432    0.0079   0.0109
allreduce          2 dcn   reduce_scatter int8/256   yes xla          0        33280    0.0013   0.0290
allreduce          3 dcn   all_gather     int8/256   yes xla          0        66560    0.0027   0.0329
allreduce          4 ici   all_gather     payload    -   xla          0      1572864    0.0157   0.0187
totals: ici=2359296 dcn=99840 pod=0 dcn_fp_equiv=393216 dcn_reduction=3.94x
predicted: 0.0915 ms step wire = bytes 0.0276 + latency 0.0560 + quant 0.0079 - hidden 0.0000 (modeled 0.0276 ms, 1 bucket) [cost model: static]
encoding: allreduce:ici.reduce_scatter[payload]>dcn.reduce_scatter[int8/256+ef]>dcn.all_gather[int8/256+ef]>ici.all_gather[payload]|s1|sync"""

GOLDEN_ZERO2_OVERLAP_2x4 = """\
wire plan  mesh=2x4  payload=1048576B (itemsize 4)
knobs: quantized=off block=256 zero_stage=2 overlap=on hierarchical=off streams=2 fusion_threshold=67108864 quantized_pod=off
collective       leg level primitive      wire       ef  backend stream    bytes/dev  model ms  pred ms
reduce_scatter     1 flat  reduce_scatter payload    -   xla          0       917504    0.0131   0.0411
all_gather         1 flat  all_gather     payload    -   xla          0      1835008    0.0262   0.0542
totals: ici=2359296 dcn=393216 pod=0 dcn_fp_equiv=393216 dcn_reduction=1.00x
predicted: 0.0953 ms step wire = bytes 0.0393 + latency 0.0560 + quant 0.0000 - hidden 0.0000 (modeled 0.0393 ms, 1 bucket) [cost model: static]
encoding: reduce_scatter:flat.reduce_scatter[payload]|s2|ovl + tail@all_gather:flat.all_gather[payload]|s2|ovl"""


class TestGoldenTables:
    def test_quantized_allreduce_table(self):
        sp = describe_plan(quantized=True, mesh_shape=(2, 4),
                           fusion_threshold_bytes=64 * 1024 * 1024,
                           quant_block=256)
        assert sp.table(payload_bytes=1 << 20) == GOLDEN_QUANTIZED_2x4

    def test_zero2_overlap_table(self):
        sp = describe_plan(zero_stage=2, overlap=True, num_comm_streams=2,
                           quantized=False, mesh_shape=(2, 4),
                           fusion_threshold_bytes=64 * 1024 * 1024,
                           quant_block=256)
        assert sp.table(payload_bytes=1 << 20) == GOLDEN_ZERO2_OVERLAP_2x4

    def test_quantized_reduction_matches_recorded_wire_ratio(self):
        # The 3.94x DCN reduction the PR-2 bench recorded is a cost-model
        # consequence, not a coincidence — the table must keep saying it.
        assert "dcn_reduction=3.94x" in GOLDEN_QUANTIZED_2x4


# ---------------------------------------------------------------------------
# Equivalence matrix, wire level: the compiler output is bit-identical to
# the pre-refactor bespoke bodies (copied here verbatim as references).
# ---------------------------------------------------------------------------


def _ref_tree_psum(x, local_axis=basics.LOCAL_AXIS,
                   cross_axis=basics.CROSS_AXIS):
    """Reference copy of the pre-plan hierarchical allreduce body."""
    shard = lax.psum_scatter(x, local_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, cross_axis)
    li = lax.axis_index(local_axis)
    full = jnp.zeros(x.shape, x.dtype)
    full = lax.dynamic_update_slice_in_dim(
        full, shard, li * shard.shape[0], 0)
    return lax.psum(full, local_axis)


def _ref_quant_allreduce(x, residual, blk, nl, nc,
                         local_axis=basics.LOCAL_AXIS,
                         cross_axis=basics.CROSS_AXIS):
    """Reference copy of the pre-plan quantized hierarchical allreduce
    body (monolithic hops 1-4, padded-array error feedback)."""
    corrected = x if residual is None else x + residual.astype(x.dtype)
    n = int(np.prod(x.shape, dtype=np.int64))
    flat = jnp.ravel(corrected)
    sn = n // nl
    seg = sn // nc
    shard = lax.psum_scatter(flat, local_axis, scatter_dimension=0,
                             tiled=True)
    segs = shard.reshape(nc, seg).astype(jnp.float32)
    pad = (-seg) % blk
    if pad:
        segs = jnp.concatenate(
            [segs, jnp.zeros((nc, pad), jnp.float32)], axis=1)
    nb = segs.shape[1] // blk
    blocks = segs.reshape(nc, nb, blk)
    scales = Z._block_scales(blocks)
    q = jnp.clip(jnp.round(blocks / scales[..., None]),
                 -127, 127).astype(jnp.int8)
    err1 = blocks - q.astype(jnp.float32) * scales[..., None]
    qT = lax.all_to_all(q, cross_axis, split_axis=0, concat_axis=0,
                        tiled=True)
    sT = lax.all_to_all(scales, cross_axis, split_axis=0, concat_axis=0,
                        tiled=True)
    acc = jnp.sum(qT.astype(jnp.float32) * sT[..., None], axis=0)
    s2 = Z._block_scales(acc)
    q2 = jnp.clip(jnp.round(acc / s2[:, None]), -127, 127).astype(jnp.int8)
    err2 = acc - q2.astype(jnp.float32) * s2[:, None]
    ci = lax.axis_index(cross_axis)
    qfull = lax.dynamic_update_slice_in_dim(
        jnp.zeros((nc, nb, blk), jnp.int8), q2[None], ci, 0)
    sfull = lax.dynamic_update_slice_in_dim(
        jnp.zeros((nc, nb), jnp.float32), s2[None], ci, 0)
    qg = lax.psum(qfull, cross_axis)
    sg = lax.psum(sfull, cross_axis)
    shard_red = (qg.astype(jnp.float32) * sg[..., None]).reshape(
        nc, nb * blk)[:, :seg].reshape(sn).astype(x.dtype)
    li = lax.axis_index(local_axis)
    full = jnp.zeros((n,), x.dtype)
    full = lax.dynamic_update_slice_in_dim(full, shard_red, li * sn, 0)
    out = lax.psum(full, local_axis).reshape(x.shape)
    if residual is None:
        return out, None
    rows = jnp.arange(nc)[:, None, None]
    err_all = err1 + jnp.where(rows == ci, err2[None], 0.0)
    err_sh = err_all.reshape(nc, nb * blk)[:, :seg].reshape(sn)
    res_full = lax.dynamic_update_slice_in_dim(
        jnp.zeros((n,), jnp.float32), err_sh, li * sn, 0)
    return out, res_full.reshape(x.shape).astype(residual.dtype)


class TestWireEquivalence:
    """Compiler output vs the pre-refactor bodies, bitwise."""

    def _run(self, fn, in_specs, out_specs, *args):
        return hvd.shard_map(fn, mesh=mesh_2x4(), in_specs=in_specs,
                             out_specs=out_specs)(*args)

    def test_tree_psum_bit_identical(self):
        # Flat per-rank payloads with dim 0 divisible by local_size, so
        # the tree path engages (not its non-divisible flat fallback).
        x = np.random.RandomState(0).randn(8, 256).astype(np.float32)
        spec = P(hvd.HVD_AXES)
        got = self._run(
            lambda xs: hvd.allreduce(xs[0], op=hvd.Sum,
                                     hierarchical=True),
            (spec,), P(), x)
        ref = self._run(lambda xs: _ref_tree_psum(xs[0]), (spec,), P(), x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        np.testing.assert_allclose(np.asarray(got), x.sum(axis=0),
                                   rtol=1e-4, atol=1e-5)

    def test_tree_psum_nondivisible_falls_back_flat(self):
        # dim 0 = 1 per rank (not divisible by local_size=4): the tree
        # plan's fallback leg must equal the flat psum bitwise — the
        # pre-refactor remainder contract.
        x = np.random.RandomState(5).randn(8, 7).astype(np.float32)
        spec = P(hvd.HVD_AXES)
        got = self._run(
            lambda xs: hvd.allreduce(xs, op=hvd.Sum, hierarchical=True),
            (spec,), P(), x)
        ref = self._run(
            lambda xs: lax.psum(xs, (basics.CROSS_AXIS,
                                     basics.LOCAL_AXIS)),
            (spec,), P(), x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.parametrize("with_ef", [False, True])
    def test_quantized_allreduce_bit_identical(self, with_ef):
        rng = np.random.RandomState(1)
        x = rng.randn(8, 1024).astype(np.float32)
        res = (rng.randn(8, 1024).astype(np.float32) * 1e-3
               if with_ef else None)
        spec = P(hvd.HVD_AXES)

        def got_fn(xs, rs=None):
            if with_ef:
                out, nr = hvd.quantized_allreduce(xs, rs, op=hvd.Sum,
                                                  block=256)
                return out, nr
            return hvd.allreduce(xs, op=hvd.Sum, quantized=True,
                                 block=256)

        def ref_fn(xs, rs=None):
            out, nr = _ref_quant_allreduce(xs, rs, 256, nl=4, nc=2)
            return (out, nr) if with_ef else out

        if with_ef:
            got = self._run(got_fn, (spec, spec), (P(), spec), x, res)
            ref = self._run(ref_fn, (spec, spec), (P(), spec), x, res)
            np.testing.assert_array_equal(np.asarray(got[0]),
                                          np.asarray(ref[0]))
            np.testing.assert_array_equal(np.asarray(got[1]),
                                          np.asarray(ref[1]))
        else:
            got = self._run(got_fn, (spec,), P(), x)
            ref = self._run(ref_fn, (spec,), P(), x)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(ref))

    def test_quantized_rs_ag_split_telescopes_to_allreduce(self):
        # The ZeRO wire pair (rs plan + ag plan, no update in between)
        # must reproduce the quantized allreduce's stateless value
        # exactly for replicated-by-construction inputs: same legs, same
        # order, split in half.
        rng = np.random.RandomState(2)
        flat = rng.randn(N * 512).astype(np.float32)
        spec = P(hvd.HVD_AXES)
        xs = np.broadcast_to(flat, (N,) + flat.shape).copy()

        def split_fn(xrow):
            x = xrow[0]
            shard = hvd.reduce_scatter(x, op=hvd.Sum, quantized=True,
                                       block=256)
            return hvd.all_gather(shard, quantized=True, block=256)

        got = self._run(split_fn, (spec,), P(), xs)
        assert np.asarray(got).shape == flat.shape
        # Structure check: the wire actually moved int8 on DCN (the
        # accounting's fp-equivalent ratio is ~3.94x).
        with hvd.record_wire_stats() as ws:
            jax.jit(hvd.shard_map(split_fn, mesh=mesh_2x4(),
                                  in_specs=(spec,),
                                  out_specs=P())).lower(xs)
        assert ws.dcn_reduction == pytest.approx(3.94, abs=0.1)

    def test_flat_psum_unchanged_by_default(self):
        # Default knobs: the plan is the single flat psum — identical to
        # calling lax.psum directly.
        x = np.random.RandomState(3).randn(8, 64).astype(np.float32)
        spec = P(hvd.HVD_AXES)
        got = self._run(lambda xs: hvd.allreduce(xs, op=hvd.Sum),
                        (spec,), P(), x)
        ref = self._run(lambda xs: lax.psum(xs, hvd.HVD_AXES),
                        (spec,), P(), x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_explicit_plan_equals_boolean_knobs(self):
        x = np.random.RandomState(4).randn(8, 512).astype(np.float32)
        spec = P(hvd.HVD_AXES)
        sp = describe_plan(quantized=True, mesh_shape=(2, 4))
        got = self._run(
            lambda xs: hvd.allreduce(xs, op=hvd.Sum, plan=sp.gradient),
            (spec,), P(), x)
        ref = self._run(
            lambda xs: hvd.allreduce(xs, op=hvd.Sum, quantized=True),
            (spec,), P(), x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# Equivalence matrix, optimizer level: every knob combination still
# trains, and the plan-space invariants hold (overlap placement is
# bit-identical to sync; a threaded StepPlan is bit-identical to the
# boolean knobs it encodes).
# ---------------------------------------------------------------------------


def _loss_fn(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)


def _train(knobs, steps=3, seed=0):
    rng = np.random.RandomState(seed)
    d = 5
    x = rng.randn(96, d).astype(np.float32)
    y = (x @ rng.randn(d, 1).astype(np.float32)).astype(np.float32)
    params = {"w": jnp.zeros((d, 1)), "b": jnp.zeros((1,))}
    zero = knobs.get("zero_stage", 0) > 0
    via_plan = knobs.pop("via_plan", False)
    # Reduce-in-optimizer structure everywhere (the canonical bench/ZeRO
    # step shape): raw per-rank local gradients reach the optimizer, so
    # the gradient wire under test is ALWAYS the optimizer's plan.
    vg = hvd.value_and_grad(_loss_fn, reduce=False)
    if via_plan:
        sp = describe_plan(mesh_shape=(2, 4), **knobs)
        tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                      plan=sp)
    else:
        tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                      **knobs)
    state = tx.init(params)
    mesh = mesh_2x4()
    if zero:
        sspec = hvd.zero_state_pspecs(state)
        state = jax.device_put(
            state,
            jax.tree.map(lambda s: NamedSharding(mesh, s), sspec))
    elif knobs.get("quantized"):
        sspec = hvd.QuantizedEFState(
            inner=jax.tree.map(lambda _: P(), state.inner),
            residual=jax.tree.map(lambda _: P(hvd.HVD_AXES),
                                  state.residual))
        state = jax.device_put(
            state,
            jax.tree.map(lambda s: NamedSharding(mesh, s), sspec))
    else:
        sspec = jax.tree.map(lambda _: P(), state)

    @jax.jit
    def step(params, state, xb, yb):
        def spmd(params, state, xb, yb):
            loss, grads = vg(params, (xb, yb))
            updates, ns = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), ns, \
                hvd.allreduce(loss)

        return hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), sspec, P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(), sspec, P()))(params, state, xb, yb)

    losses = []
    bs = 16
    for i in range(steps):
        params, state, loss = step(params, state,
                                   jnp.asarray(x[i * bs:(i + 1) * bs]),
                                   jnp.asarray(y[i * bs:(i + 1) * bs]))
        losses.append(float(loss))
    return params, losses


_MATRIX = [
    dict(quantized=False, zero_stage=0, hierarchical=False),
    dict(quantized=False, zero_stage=0, hierarchical=True),
    dict(quantized=True, zero_stage=0),
    dict(quantized=False, zero_stage=2),
    dict(quantized=True, zero_stage=2),
    dict(quantized=False, zero_stage=3),
]


class TestOptimizerMatrix:
    @pytest.mark.parametrize("knobs", _MATRIX, ids=lambda k: (
        f"q{int(k.get('quantized', False))}"
        f"z{k.get('zero_stage', 0)}"
        f"h{int(k.get('hierarchical') or 0)}"))
    def test_overlap_placement_is_bit_identical(self, knobs):
        """Every knob point: overlap-on == overlap-off, bitwise (stream
        placement is a plan attribute, never math — the invariant the
        pre-refactor paths guaranteed and the compiler must keep)."""
        if knobs.get("zero_stage", 0) == 3:
            pytest.skip("stage 3 restructures the loop (params are "
                        "shards) — covered by test_zero's stage suite")
        p_sync, l_sync = _train({**knobs, "overlap": False})
        p_ovl, l_ovl = _train({**knobs, "overlap": True,
                               "num_comm_streams": 2})
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), p_sync, p_ovl)
        assert l_sync == l_ovl
        assert l_sync[-1] < l_sync[0]  # it actually trains

    @pytest.mark.parametrize("knobs", [
        dict(quantized=False, zero_stage=0, hierarchical=False),
        dict(quantized=True, zero_stage=0),
        dict(quantized=False, zero_stage=2),
    ], ids=("plain", "quant", "zero2"))
    def test_step_plan_thread_matches_booleans(self, knobs):
        """DistributedOptimizer(plan=describe_plan(**knobs)) trains
        bit-identically to the boolean spelling of the same knobs."""
        p_bool, l_bool = _train(dict(knobs))
        p_plan, l_plan = _train({**knobs, "via_plan": True})
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), p_bool, p_plan)
        assert l_bool == l_plan


# ---------------------------------------------------------------------------
# 3-level (pods) smoke: plan-compiled allreduce on an emulated 2x2x2
# (pod, cross, local) mesh + --mesh-shape parsing.
# ---------------------------------------------------------------------------


class TestThreeLevel:
    @pytest.fixture()
    def mesh_2x2x2(self):
        grid = np.array(jax.devices()[:N]).reshape(2, 2, 2)
        return Mesh(grid, basics.ALL_AXES)

    def test_flat_allreduce_smoke(self, mesh_2x2x2):
        x = np.arange(16, dtype=np.float32).reshape(8, 2)
        spec = P(basics.ALL_AXES)
        out = hvd.shard_map(
            lambda xs: hvd.allreduce(xs, op=hvd.Sum),
            mesh=mesh_2x2x2, in_specs=(spec,), out_specs=P())(x)
        np.testing.assert_allclose(np.asarray(out)[0], x.sum(axis=0))

    def test_tree_allreduce_smoke(self, mesh_2x2x2):
        # Per-rank payload dim 0 divisible by local_size=2 so the
        # 3-level [ici.rs > dcn.psum > pod.psum > ici.ag] ladder engages.
        x = np.random.RandomState(0).randn(8, 32).astype(np.float32)
        spec = P(basics.ALL_AXES)
        out = hvd.shard_map(
            lambda xs: hvd.allreduce(xs[0], op=hvd.Sum,
                                     hierarchical=True),
            mesh=mesh_2x2x2, in_specs=(spec,), out_specs=P())(x)
        np.testing.assert_allclose(np.asarray(out), x.sum(axis=0),
                                   rtol=1e-5)

    def test_rank_covers_pods(self, mesh_2x2x2):
        spec = P(basics.ALL_AXES)
        ranks = hvd.shard_map(
            lambda: hvd.rank()[None],
            mesh=mesh_2x2x2, in_specs=(), out_specs=spec)()
        assert sorted(np.asarray(ranks).ravel().tolist()) == list(range(8))

    def test_hvd_axes_in_trace_includes_pod(self, mesh_2x2x2):
        seen = {}

        def probe():
            seen["axes"] = C._hvd_axes_in_trace()
            return jnp.zeros(())

        hvd.shard_map(probe, mesh=mesh_2x2x2, in_specs=(),
                      out_specs=P())()
        assert seen["axes"] == basics.ALL_AXES

    def test_build_mesh_pods_one_collapses_to_2d(self):
        m = basics._build_mesh(jax.devices()[:N], (2, 4, 1))
        assert m.devices.shape == (2, 4)
        m3 = basics._build_mesh(jax.devices()[:N], (2, 2, 2))
        assert m3.devices.shape == (2, 2, 2)
        assert m3.axis_names == basics.ALL_AXES
