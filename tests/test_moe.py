"""Expert-parallel MoE (docs/moe.md).

The a2a wire plan must validate/lower/account like every other leg, the
routing must be deterministic with documented overflow semantics, the
layer must be exact against dense references through gradients, the
``hvd_ep`` axis must isolate expert gradients while composing with
ZeRO, and the moe knobs must ride the autotune machinery (schema v9).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.moe import (
    EXPERT_LEAVES,
    default_a2a_plan,
    ep_mean_dense_grads,
    ep_param_pspecs,
    ep_stack_params,
    moe_capacity,
    moe_ef_residuals,
    moe_ffn,
    moe_positions,
    moe_router,
)
from horovod_tpu.ops.collective_ops import record_wire_stats
from horovod_tpu.plan import (
    ALL_TO_ALL,
    Leg,
    PlanError,
    WirePlan,
    a2a_plan,
    ep_a2a_level,
    predict_a2a_bytes,
)

E, C, F, K = 4, 8, 16, 2
EPALL = (hvd.EP_AXIS,) + hvd.HVD_AXES


def dense_params(seed=0):
    rs = np.random.RandomState(seed)
    return {
        "router": jnp.asarray(rs.randn(C, E) * 0.1, jnp.float32),
        "w1": jnp.asarray(rs.randn(E, C, F) * 0.1, jnp.float32),
        "b1": jnp.asarray(rs.randn(E, F) * 0.01, jnp.float32),
        "w2": jnp.asarray(rs.randn(E, F, C) * 0.1, jnp.float32),
        "b2": jnp.asarray(rs.randn(E, C) * 0.01, jnp.float32),
    }


def local_view(pt):
    return {k: (v[0] if k in EXPERT_LEAVES else v)
            for k, v in pt.items()}


def ep_mesh(ep=E, data=(2, 1)):
    hvd.shutdown()
    hvd.init(devices=jax.devices(), mesh_shape=data, ep_size=ep)
    return hvd.mesh()


def restore_mesh():
    hvd.shutdown()
    hvd.init(devices=jax.devices())


# ---------------------------------------------------------------------------
# IR: the a2a primitive.
# ---------------------------------------------------------------------------


class TestA2AIR:
    def test_a2a_plan_encodes(self):
        p = a2a_plan("dcn", quantized=True, block=256,
                     error_feedback=True)
        assert p.encode() == "a2a:dcn.all_to_all[int8/256+ef]|s1|sync"
        assert a2a_plan("ici").encode() == \
            "a2a:ici.all_to_all[payload]|s1|sync"

    def test_int8_on_ici_a2a_rejected(self):
        with pytest.raises(PlanError, match="non-DCN"):
            WirePlan("a2a", (Leg("ici", ALL_TO_ALL, "int8",
                                 block=256),)).validate()

    def test_a2a_leg_outside_a2a_plan_rejected(self):
        with pytest.raises(PlanError, match="only belongs to an 'a2a'"):
            WirePlan("allreduce", (Leg("dcn", ALL_TO_ALL),)).validate()

    def test_non_a2a_leg_inside_a2a_plan_rejected(self):
        with pytest.raises(PlanError, match="only all_to_all"):
            WirePlan("a2a", (Leg("dcn", "psum"),)).validate()

    def test_multi_leg_a2a_plan_rejected(self):
        with pytest.raises(PlanError, match="exactly ONE exchange"):
            WirePlan("a2a", (Leg("dcn", ALL_TO_ALL),
                             Leg("dcn", ALL_TO_ALL))).validate()

    def test_flat_a2a_rejected(self):
        with pytest.raises(PlanError, match="LINK CLASS"):
            WirePlan("a2a", (Leg("flat", ALL_TO_ALL),)).validate()

    def test_a2a_level_from_mesh(self):
        assert ep_a2a_level((2, 2)) == "dcn"
        assert ep_a2a_level((1, 4)) == "ici"
        assert ep_a2a_level((2, 2, 2)) == "pod"
        # quantization forced off on an ICI-class hop
        from horovod_tpu.plan import derive_a2a

        p = derive_a2a(mesh_shape=(1, 4), quantized=True)
        assert not p.is_quantized


# ---------------------------------------------------------------------------
# Routing: determinism + capacity overflow.
# ---------------------------------------------------------------------------


class TestRouting:
    def test_deterministic_routing_and_positions(self):
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(32, C), jnp.float32)
        p = dense_params(1)
        e1, g1, lb1, z1, _ = moe_router(x, p["router"], topk=K)
        e2, g2, lb2, z2, _ = moe_router(x, p["router"], topk=K)
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        pos1, keep1 = moe_positions(e1, E, 8)
        pos2, keep2 = moe_positions(e2, E, 8)
        np.testing.assert_array_equal(np.asarray(pos1), np.asarray(pos2))
        np.testing.assert_array_equal(np.asarray(keep1),
                                      np.asarray(keep2))
        # renormalized top-k gates sum to one
        np.testing.assert_allclose(np.asarray(jnp.sum(g1, -1)), 1.0,
                                   rtol=1e-5)

    def test_positions_choice_major(self):
        # Every token's FIRST choice ranks before any second choice:
        # 3 tokens all first-choosing expert 0, second-choosing expert 0
        # again via a crafted [N, K] — first choices take slots 0..2.
        experts = jnp.asarray([[0, 1], [0, 1], [0, 1]], jnp.int32)
        pos, keep = moe_positions(experts, E, 8)
        np.testing.assert_array_equal(np.asarray(pos[:, 0]), [0, 1, 2])
        assert bool(jnp.all(keep))

    def test_capacity_overflow_drops_deterministically(self):
        # 5 tokens, all routed (top-1) to expert 0, capacity 2: the
        # FIRST two tokens in order keep, the rest drop.
        experts = jnp.zeros((5, 1), jnp.int32)
        pos, keep = moe_positions(experts, E, 2)
        np.testing.assert_array_equal(np.asarray(keep[:, 0]),
                                      [True, True, False, False, False])
        # and the dropped tokens pass through as ZERO layer output
        x = jnp.asarray(np.random.RandomState(0).randn(5, C),
                        jnp.float32)
        forced = jnp.concatenate(
            [jnp.full((5, 1), 1e3, jnp.float32),
             jnp.full((5, E - 1), -1e3, jnp.float32)], axis=1)
        # capacity_factor chosen so capacity == ceil(K*5*cf/E) == 2
        cf = 2 * E / (K * 5)
        y, aux, _ = moe_ffn(x, dense_params(0), topk=K,
                            capacity_factor=cf,
                            router_logits=forced)
        assert moe_capacity(5, E, cf, K) == 2
        got = np.asarray(y)
        assert np.abs(got[2:]).max() == 0.0        # dropped -> zeros
        assert np.abs(got[:2]).max() > 0.0
        assert float(aux.dropped_fraction) > 0.0

    def test_aux_losses_finite_and_balanced_case(self):
        x = jnp.asarray(np.random.RandomState(1).randn(64, C),
                        jnp.float32)
        p = dense_params(2)
        _, _, lb, z, probs = moe_router(x, p["router"], topk=K)
        assert np.isfinite(float(lb)) and np.isfinite(float(z))
        # perfectly uniform probs minimize the Switch loss at 1.0
        uni = jnp.zeros((64, E), jnp.float32)
        _, _, lb_u, _, _ = moe_router(x, p["router"], topk=K,
                                      router_logits=uni)
        assert float(lb_u) == pytest.approx(1.0, abs=1e-5)


# ---------------------------------------------------------------------------
# Exactness: forced-routing parity + top-2 gradient parity.
# ---------------------------------------------------------------------------


def _dense_reference(pt, x, experts, gates):
    """The same math as moe_ffn, spelled as dense einsums with no
    dispatch buffer: y_n = sum_k gate_nk * FFN_{e_nk}(x_n)."""
    import flax.linen as fnn

    h = fnn.gelu(jnp.einsum("nc,ecf->enf", x, pt["w1"])
                 + pt["b1"][:, None])
    y_all = jnp.einsum("enf,efc->enc", h, pt["w2"]) \
        + pt["b2"][:, None]                           # [E, N, C]
    oh = jax.nn.one_hot(experts, E, dtype=jnp.float32)  # [N, K, E]
    sel = jnp.einsum("nke,enc->nkc", oh, y_all)
    return jnp.sum(sel * gates[..., None], axis=1)


class TestExactness:
    def test_expert0_identity_gating_matches_dense(self):
        """Every token routed to expert 0 with gate 1 over the hvd_ep
        mesh == the dense expert-0 FFN (the a2a wire is exact)."""
        try:
            mesh = ep_mesh()
            pt = dense_params(5)
            stacked = ep_stack_params(pt, E)
            pspec = ep_param_pspecs(stacked)
            rs = np.random.RandomState(7)
            x = jnp.asarray(rs.randn(8 * 16, C), jnp.float32)

            def spmd(p, xb):
                n = xb.shape[0]
                forced = jnp.concatenate(
                    [jnp.full((n, 1), 1e3, jnp.float32),
                     jnp.zeros((n, E - 1), jnp.float32)], axis=1)
                y, _, _ = moe_ffn(xb, local_view(p), topk=K,
                                  capacity_factor=float(E),
                                  ep_axis=hvd.EP_AXIS,
                                  router_logits=forced)
                return y

            f = jax.jit(hvd.shard_map(
                spmd, mesh=mesh, in_specs=(pspec, P(EPALL)),
                out_specs=P(EPALL)))
            got = np.asarray(f(stacked, x))
            import flax.linen as fnn

            want = np.asarray(
                fnn.gelu(x @ pt["w1"][0] + pt["b1"][0]) @ pt["w2"][0]
                + pt["b2"][0])
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        finally:
            restore_mesh()

    def test_top2_gradient_parity_vs_dense_einsum(self):
        """Real top-2 routing (no drops): moe_ffn's value AND gradients
        match the dense einsum reference computing the identical math
        with no dispatch buffer."""
        pt = dense_params(9)
        rs = np.random.RandomState(11)
        x = jnp.asarray(rs.randn(32, C), jnp.float32)

        def moe_loss(p):
            y, _, _ = moe_ffn(x, p, topk=K, capacity_factor=float(E))
            return jnp.sum(y ** 2)

        def ref_loss(p):
            experts, gates, _, _, _ = moe_router(x, p["router"], topk=K)
            y = _dense_reference(p, x, experts, gates)
            return jnp.sum(y ** 2)

        v1, g1 = jax.value_and_grad(moe_loss)(pt)
        v2, g2 = jax.value_and_grad(ref_loss)(pt)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6),
            g1, g2)

    def test_moe_layer_module_sows_diagnostics(self):
        from horovod_tpu.moe import MoELayer

        x = jnp.asarray(np.random.RandomState(0).randn(2, 8, C),
                        jnp.float32)
        layer = MoELayer(num_experts=E, d_ff=F, topk=K,
                         capacity_factor=4.0)
        params = layer.init(jax.random.PRNGKey(0), x)
        y, state = layer.apply(params, x, mutable=["intermediates"])
        assert y.shape == x.shape
        inter = state["intermediates"]
        assert "moe_aux_loss" in inter and "moe_z_loss" in inter
        load = np.asarray(inter["moe_expert_load"][0])
        assert load.shape == (E,) and load.sum() > 0


# ---------------------------------------------------------------------------
# The int8+EF a2a wire.
# ---------------------------------------------------------------------------


class TestQuantizedA2A:
    def test_int8_exchange_error_bound_and_ef(self):
        """One int8 exchange's error is bounded by the per-block scale;
        with error feedback the bias telescopes instead of compounding
        (the running output sum tracks the exact sum)."""
        try:
            mesh = ep_mesh()
            from horovod_tpu.plan import compiler as _compiler

            blk = 64
            plan_q = a2a_plan("dcn", quantized=True, block=blk,
                              error_feedback=True)
            plan_x = a2a_plan("dcn")
            rs = np.random.RandomState(3)
            buf = jnp.asarray(rs.randn(8, E, 16, C), jnp.float32)

            def spmd(b):
                x = b[0]
                exact, _ = _compiler.lower_a2a(plan_x, x,
                                               axis=hvd.EP_AXIS)
                q1, _ = _compiler.lower_a2a(plan_q, x,
                                            axis=hvd.EP_AXIS)
                # EF: T exchanges of the SAME buffer, residual threaded
                res = jnp.zeros_like(x)
                acc = jnp.zeros_like(x)
                for _i in range(4):
                    out, res = _compiler.lower_a2a(
                        plan_q, x, axis=hvd.EP_AXIS, residual=res)
                    acc = acc + out
                return (exact[None], q1[None], acc[None])

            f = jax.jit(hvd.shard_map(
                spmd, mesh=mesh,
                in_specs=(P(EPALL),),
                out_specs=(P(EPALL), P(EPALL), P(EPALL))))
            exact, q1, acc = (np.asarray(v) for v in f(buf))
            scale_bound = np.abs(buf).max() / 127.0
            err1 = np.abs(q1 - exact).max()
            assert err1 <= scale_bound + 1e-6
            assert err1 > 0                       # int8 actually engaged
            # telescoping: |sum of 4 EF outputs - 4*exact| stays at the
            # single-exchange bound, not 4x it
            err_acc = np.abs(acc - 4 * exact).max()
            assert err_acc <= 2 * scale_bound + 1e-6
        finally:
            restore_mesh()

    def test_quantized_a2a_gradients_flow(self):
        """The int8 exchange's custom VJP keeps gradients alive (the
        backward rides the same int8 wire; a plain round would zero
        them)."""
        try:
            mesh = ep_mesh()
            from horovod_tpu.plan import compiler as _compiler

            plan_q = a2a_plan("dcn", quantized=True, block=64)
            rs = np.random.RandomState(5)
            buf = jnp.asarray(rs.randn(8, E, 4, C), jnp.float32)

            def spmd(b):
                def loss(x):
                    out, _ = _compiler.lower_a2a(plan_q, x,
                                                 axis=hvd.EP_AXIS)
                    return jnp.sum(out ** 2)

                g = jax.grad(loss)(b[0])
                return jnp.sum(jnp.abs(g))[None]

            f = jax.jit(hvd.shard_map(
                spmd, mesh=mesh, in_specs=(P(EPALL),),
                out_specs=P(EPALL)))
            gsum = np.asarray(f(buf))
            assert (gsum > 0).all()
        finally:
            restore_mesh()


# ---------------------------------------------------------------------------
# The hvd_ep mesh: geometry + expert-grad isolation (ZeRO-2 compose).
# ---------------------------------------------------------------------------


class TestEPMesh:
    def test_ep_mesh_geometry(self):
        try:
            mesh = ep_mesh(ep=2, data=(2, 2))
            assert hvd.ep_size() == 2
            assert hvd.pp_size() == 1
            assert hvd.data_mesh_shape() == (2, 2)
            assert mesh.axis_names == (hvd.EP_AXIS, hvd.CROSS_AXIS,
                                       hvd.LOCAL_AXIS)
            from horovod_tpu.common import basics

            assert basics.world_axes() == hvd.HVD_AXES
            assert "ep2" in basics.mesh_geometry()
        finally:
            restore_mesh()

    def test_ep_composes_with_pp_on_4d_mesh(self):
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(1, 2),
                     ep_size=2, pp_stages=2)
            from horovod_tpu.common import basics

            assert hvd.pp_size() == 2
            assert hvd.ep_size() == 2
            assert hvd.data_mesh_shape() == (1, 2)
            assert hvd.mesh().axis_names == (
                hvd.PP_AXIS, hvd.EP_AXIS, hvd.CROSS_AXIS,
                hvd.LOCAL_AXIS)
            # pp/ep are NOT data axes: shards and gradient collectives
            # stay on (cross, local) per (stage, expert-group) cell.
            assert basics.world_axes() == hvd.HVD_AXES
            assert "pp2.ep2" in basics.mesh_geometry()
        finally:
            restore_mesh()

    def test_ep_does_not_compose_with_pods(self):
        hvd.shutdown()
        try:
            with pytest.raises(ValueError, match="3-level"):
                hvd.init(devices=jax.devices(), mesh_shape=(1, 2, 2),
                         ep_size=2)
        finally:
            restore_mesh()

    def test_moe_knob_validation(self):
        try:
            ep_mesh(ep=2, data=(2, 2))
            # experts must divide by the live ep axis
            with pytest.raises(ValueError, match="hvd_ep"):
                hvd.DistributedOptimizer(optax.sgd(0.1), moe_experts=3)
            with pytest.raises(ValueError, match="capacity"):
                hvd.DistributedOptimizer(optax.sgd(0.1), moe_experts=4,
                                         moe_capacity_factor=0.0)
            with pytest.raises(ValueError, match="moe_topk"):
                hvd.DistributedOptimizer(optax.sgd(0.1), moe_experts=4,
                                         moe_topk=9)
            hvd.DistributedOptimizer(optax.sgd(0.1), moe_experts=4,
                                     moe_capacity_factor=1.25,
                                     moe_topk=2)
            hvd.value_and_grad(lambda p: p, moe_experts=4,
                               moe_capacity_factor=1.25, moe_topk=2)
        finally:
            restore_mesh()

    def test_expert_grad_isolation_zero2_one_step_parity(self):
        """EP x ZeRO-2: one SGD-momentum step on the hvd_ep mesh — the
        batch sharded over (ep, cross, local), expert grads reducing
        ONLY within their expert's data group — equals the dense
        single-device step on the global-mean gradient."""
        try:
            mesh = ep_mesh(ep=2, data=(2, 2))
            ep = 2
            pt = dense_params(21)
            stacked = ep_stack_params(pt, ep)
            pspec = ep_param_pspecs(stacked)
            rs = np.random.RandomState(23)
            Ng = 8 * 16
            x = jnp.asarray(rs.randn(Ng, C), jnp.float32)
            y = jnp.asarray(rs.randn(Ng, C), jnp.float32)
            cf = float(E)  # no drops: distributed == global routing

            tx = hvd.DistributedOptimizer(
                optax.sgd(0.1, momentum=0.9), zero_stage=2,
                moe_experts=E, moe_capacity_factor=cf, moe_topk=K)
            sspec_of = lambda st: jax.tree.map(  # noqa: E731
                lambda l: P(EPALL) if getattr(l, "ndim", 0) >= 1
                else P(), st)
            state_tpl = tx.init(local_view(stacked))

            def init_spmd(p):
                return tx.init(local_view(p))

            state = jax.jit(hvd.shard_map(
                init_spmd, mesh=mesh, in_specs=(pspec,),
                out_specs=sspec_of(state_tpl)))(stacked)
            sspec = sspec_of(state)

            def step_spmd(p, st, xb, yb):
                lp = local_view(p)

                def loss_fn(q):
                    out, _, _ = moe_ffn(xb, q, topk=K,
                                        capacity_factor=cf,
                                        ep_axis=hvd.EP_AXIS)
                    return jnp.mean((out - yb) ** 2)

                loss, g = jax.value_and_grad(loss_fn)(lp)
                g = ep_mean_dense_grads(g)
                upd, st2 = tx.update(g, st, lp)
                new = optax.apply_updates(lp, upd)
                loss = lax.pmean(loss, EPALL)
                # Re-establish the router's ep replication by
                # construction (the ZeRO buckets mixed ep-varying
                # expert leaves into the gather).
                rep = lax.axis_index(hvd.EP_AXIS)
                new_router = lax.psum(
                    jnp.where(rep == 0, new["router"],
                              jnp.zeros_like(new["router"])),
                    hvd.EP_AXIS)
                new_p = {k: (v[None] if k in EXPERT_LEAVES else v)
                         for k, v in new.items()}
                new_p["router"] = new_router
                return loss, new_p, st2

            data = P(EPALL)
            step = jax.jit(hvd.shard_map(
                step_spmd, mesh=mesh,
                in_specs=(pspec, sspec, data, data),
                out_specs=(P(), pspec, sspec)))
            loss, new_stacked, state = step(stacked, state, x, y)

            # dense single-device reference on the global-mean gradient
            def ref_loss(q):
                out, _, _ = moe_ffn(x, q, topk=K, capacity_factor=cf)
                return jnp.mean((out - y) ** 2)

            want_loss, g_ref = jax.value_and_grad(ref_loss)(pt)
            np.testing.assert_allclose(float(loss), float(want_loss),
                                       rtol=1e-5)
            ref_tx = optax.sgd(0.1, momentum=0.9)
            upd, _ = ref_tx.update(g_ref, ref_tx.init(pt), pt)
            want_p = optax.apply_updates(pt, upd)
            got = jax.device_get(new_stacked)
            for k in ("w1", "b1", "w2", "b2"):
                got_full = np.concatenate(
                    [np.asarray(got[k][g]) for g in range(ep)], axis=0)
                np.testing.assert_allclose(
                    got_full, np.asarray(want_p[k]), rtol=2e-4,
                    atol=2e-6)
            np.testing.assert_allclose(
                np.asarray(got["router"]), np.asarray(want_p["router"]),
                rtol=2e-4, atol=2e-6)
            # isolation: the two ep groups hold DIFFERENT experts —
            # their updated expert weights must differ (nothing mixed
            # them across hvd_ep)
            assert not np.allclose(np.asarray(got["w1"][0]),
                                   np.asarray(got["w1"][1]))
        finally:
            restore_mesh()


# ---------------------------------------------------------------------------
# Accounting + spans.
# ---------------------------------------------------------------------------


class TestAccounting:
    def test_a2a_accounting_matches_prediction(self):
        """Trace-time a2a accounting == the router-predicted bytes of
        predict_a2a_bytes, per exchange, by construction."""
        try:
            mesh = ep_mesh()
            pt = dense_params(0)
            stacked = ep_stack_params(pt, E)
            pspec = ep_param_pspecs(stacked)
            x = jnp.asarray(np.random.RandomState(0).randn(8 * 16, C),
                            jnp.float32)
            cf = 2.0
            Nd = 16  # tokens per device
            cap = moe_capacity(Nd, E, cf, K)
            for quantized in (False, True):
                plan = a2a_plan("dcn", quantized=quantized, block=64)

                def spmd(p, xb):
                    y, _, _ = moe_ffn(xb, local_view(p), topk=K,
                                      capacity_factor=cf,
                                      ep_axis=hvd.EP_AXIS,
                                      a2a_plan=plan)
                    return y

                f = jax.jit(hvd.shard_map(
                    spmd, mesh=mesh, in_specs=(pspec, P(EPALL)),
                    out_specs=P(EPALL)))
                with record_wire_stats() as ws:
                    jax.block_until_ready(f(stacked, x))
                n = E * cap * C
                rows = predict_a2a_bytes(plan, n, 4, E)
                want = rows[0]["bytes"] * 2      # dispatch + combine
                assert ws.a2a_calls == 2
                assert ws.a2a_bytes == pytest.approx(want)
                assert ws.a2a_bytes_fp == pytest.approx(
                    rows[0]["fp_bytes"] * 2)
                if quantized:
                    assert ws.a2a_bytes < ws.a2a_bytes_fp
        finally:
            restore_mesh()

    def test_moe_spans_balanced_strict(self, tmp_path):
        from horovod_tpu.monitor import span_audit

        tl = str(tmp_path / "moe_tl.json")
        hvd.shutdown()
        import os

        os.environ["HOROVOD_TIMELINE"] = tl
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(2, 1),
                     ep_size=4)
            mesh = hvd.mesh()
            pt = dense_params(0)
            stacked = ep_stack_params(pt, E)
            pspec = ep_param_pspecs(stacked)
            x = jnp.asarray(np.random.RandomState(0).randn(8 * 8, C),
                            jnp.float32)

            def spmd(p, xb):
                y, _, _ = moe_ffn(xb, local_view(p), topk=K,
                                  capacity_factor=2.0,
                                  ep_axis=hvd.EP_AXIS)
                return y

            f = jax.jit(hvd.shard_map(
                spmd, mesh=mesh, in_specs=(pspec, P(EPALL)),
                out_specs=P(EPALL)))
            jax.block_until_ready(f(stacked, x))
        finally:
            del os.environ["HOROVOD_TIMELINE"]
            hvd.shutdown()
            hvd.init(devices=jax.devices())
        audit = span_audit.audit_spans(tl, prefix="MOE:",
                                       require_balanced=True,
                                       require_spans=True, strict=True)
        assert audit.count.get("MOE:DISPATCH", 0) == 1
        assert audit.count.get("MOE:COMBINE", 0) == 1


# ---------------------------------------------------------------------------
# Golden --dump-plan table: the a2a rows are pinned text.
# ---------------------------------------------------------------------------


class TestGoldenPlan:
    def test_dump_plan_pins_a2a_leg(self):
        sp = hvd.describe_plan(mesh_shape=(2, 2), moe_experts=4,
                               moe_topk=2, moe_capacity=1.25,
                               moe_quantized=True, quantized=False,
                               zero_stage=0, overlap=False,
                               hierarchical=False, num_comm_streams=1,
                               quant_block=256,
                               fusion_threshold_bytes=64 * 1024 * 1024,
                               quantized_pod=False, pp_stages=0)
        table = sp.table(payload_bytes=4 * 1024 * 1024)
        assert ("a2a                1 dcn   all_to_all     int8/256   "
                "yes xla          0") in table
        assert ("moe: experts=4 topk=2 capacity_factor=1.25 "
                "quantized=on (a2a rows priced per issue — dispatch + "
                "combine = 2 per layer, docs/moe.md)") in table
        assert sp.encode() == (
            "allreduce:flat.psum[payload]|s1|sync + "
            "ep4.k2@a2a:dcn.all_to_all[int8/256+ef]|s1|sync")

    def test_ici_hop_never_quantizes(self):
        sp = hvd.describe_plan(mesh_shape=(1, 4), moe_experts=2,
                               moe_quantized=True, quantized=False,
                               zero_stage=0, overlap=False,
                               hierarchical=False, pp_stages=0)
        assert sp.moe.legs[0].level == "ici"
        assert not sp.moe.is_quantized
        assert not sp.moe_quantized


# ---------------------------------------------------------------------------
# Autotune schema v9.
# ---------------------------------------------------------------------------


class TestAutotuneV9:
    def test_encode_decode_moe_segment(self):
        from horovod_tpu.autotune.parameter_manager import TunedParams
        from horovod_tpu.plan.planner import decode_tuned, encode_tuned

        p = TunedParams(moe_capacity_factor=1.5, moe_quantized=True)
        enc = encode_tuned(p, moe=True)
        assert enc == "ar.flat|fp|s1|sync|moe1.5/q8"
        d = decode_tuned(enc)
        assert d["moe_capacity_factor"] == 1.5 and d["moe_quantized"]
        # moe off: the segment (and both knobs) drop out — dead knobs
        # never split trials
        assert encode_tuned(p) == "ar.flat|fp|s1|sync"
        d0 = decode_tuned(encode_tuned(p))
        assert d0["moe_capacity_factor"] == 0.0
        assert not d0["moe_quantized"]

    def test_manager_canonicalizes_dead_moe_knobs(self):
        from horovod_tpu.autotune.parameter_manager import (
            ParameterManager, TunedParams)

        pm = ParameterManager(TunedParams(), warmup_samples=0,
                              max_samples=3, tune_moe=False)
        c = pm._canonicalize(TunedParams(moe_capacity_factor=2.0,
                                        moe_quantized=True))
        assert c.moe_capacity_factor == 0.0 and not c.moe_quantized

    def test_manager_snaps_moe_proposals(self):
        from horovod_tpu.autotune.parameter_manager import (
            ParameterManager, TunedParams)

        pm = ParameterManager(TunedParams(moe_capacity_factor=1.25),
                              warmup_samples=0, max_samples=8,
                              tune_moe=True, moe_experts=4)
        for u8 in (0.0, 0.3, 0.7, 1.0):
            p = pm._from_unit((0.5, 0.5, 0.25, 0.25, 0.25, 0.0,
                               0.0, 0.0, u8, 0.9))
            assert 1.0 <= p.moe_capacity_factor <= 2.0
            assert (p.moe_capacity_factor * 4) == int(
                p.moe_capacity_factor * 4)       # quarter-snapped
            assert p.moe_quantized
        # pre-v9 unit tuples (8 dims) still resolve
        p = pm._from_unit((0.5, 0.5, 0.25, 0.25, 0.25, 0.0, 0.0, 0.0))
        assert p.moe_capacity_factor >= 1.0

    def test_csv_roundtrip_with_moe_columns(self, tmp_path):
        from horovod_tpu.autotune.parameter_manager import (
            CSV_FIELDS, ParameterManager, TunedParams, read_log)

        assert "moe_capacity_factor" in CSV_FIELDS
        assert "moe_quantized" in CSV_FIELDS
        path = str(tmp_path / "log.csv")
        pm = ParameterManager(TunedParams(moe_capacity_factor=1.25,
                                          moe_quantized=True),
                              warmup_samples=0, max_samples=3,
                              tune_moe=True, moe_experts=4,
                              log_path=path)
        while not pm.done:
            pm.record_sample(1.0)
        rows = read_log(path)
        assert rows and rows[0]["moe_capacity_factor"] == 1.25
        assert rows[0]["moe_quantized"] is True
        assert rows[0]["plan"].endswith("|moe1.25/q8")

    def test_read_log_tolerant_of_v8_csv(self, tmp_path):
        from horovod_tpu.autotune.parameter_manager import read_log

        path = tmp_path / "v8.csv"
        path.write_text(
            "sample,fusion_threshold_bytes,quant_block,"
            "hierarchical_allreduce,zero_sharding,zero_stage,overlap,"
            "num_comm_streams,fused,pp_microbatches,pp_interleave,"
            "score_steps_per_sec,plan\n"
            "1,4194304,256,0,0,0,0,1,0,0,1,12.5,ar.flat|fp|s1|sync\n")
        rows = read_log(str(path))
        assert rows[0]["moe_capacity_factor"] == 0.0
        assert rows[0]["moe_quantized"] is False

    def test_tuned_params_from_v8_dict(self):
        from horovod_tpu.autotune.parameter_manager import TunedParams

        p = TunedParams.from_dict({
            "fusion_threshold_bytes": 4 << 20, "quant_block": 256,
            "hierarchical_allreduce": False, "zero_stage": 2,
            "overlap": True, "num_comm_streams": 2, "fused": False,
            "pp_microbatches": 8, "pp_interleave": 2})
        assert p.moe_capacity_factor == 0.0
        assert p.moe_quantized is False

    def test_shortlist_prices_moe_candidates(self):
        from horovod_tpu.plan.planner import shortlist

        rows = shortlist(8 * 1024 * 1024, mesh_shape=(2, 2),
                         tune_moe=True, moe_experts=4,
                         tune_hierarchical=False, k=8)
        assert rows
        caps = {r.params.moe_capacity_factor for r in rows}
        assert len(caps) > 1       # distinct capacity candidates priced
        assert any(r.params.moe_quantized for r in rows)
        for r in rows:
            assert r.plan.moe is not None
            assert r.cost.moe_ms > 0


# ---------------------------------------------------------------------------
# Serving: per-expert load metrics + hot-expert replication.
# ---------------------------------------------------------------------------


@pytest.mark.serve
class TestServeMoE:
    @pytest.fixture(scope="class")
    def model(self):
        from horovod_tpu.models import GPT, gpt_tiny

        cfg = gpt_tiny(dtype=jnp.float32, num_heads=8)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (1, 8)))
        params = GPT(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
        return cfg, params

    def _page_cfg(self, cfg):
        from horovod_tpu.serve.kv_cache import PageConfig

        return PageConfig(num_pages=64, page_size=4, max_slots=4,
                          pages_per_slot=16,
                          num_layers=cfg.num_layers,
                          num_heads=cfg.num_heads,
                          head_dim=cfg.d_model // cfg.num_heads)

    def test_engine_expert_load_metrics(self, model):
        from horovod_tpu.monitor import registry as _metrics
        from horovod_tpu.serve.engine import GenerationEngine
        from horovod_tpu.serve.engine import VirtualClock
        from horovod_tpu.serve.scheduler import Request

        cfg, params = model
        eng = GenerationEngine(cfg, params, self._page_cfg(cfg),
                               eos_id=1, moe_experts=4)
        reqs = [Request(prompt=[4 * i % 16, 3, 5], max_new_tokens=3,
                        arrival_time=0.0) for i in range(3)]
        eng.run(reqs, clock=VirtualClock())
        assert eng.expert_tokens.sum() > 0
        snap = _metrics.default_registry().snapshot()
        hists = {k: v for k, v in snap["histograms"].items()
                 if k.startswith("serve.expert_tokens")}
        assert hists and sum(h["count"] for h in hists.values()) > 0

    def test_hot_expert_replication_under_skew(self, model):
        from horovod_tpu.serve.replica import ReplicaSet
        from horovod_tpu.serve.engine import VirtualClock
        from horovod_tpu.serve.scheduler import Request

        cfg, params = model
        rset = ReplicaSet(cfg, params, self._page_cfg(cfg),
                          n_replicas=2, eos_id=1, moe_experts=4,
                          hot_expert_factor=1.5, rebalance_every=2)
        # Skewed traffic: EVERY consumed token routes to expert 0
        # (all prompt tokens are multiples of 4; max_new_tokens=1 means
        # no sampled token is ever fed back).
        reqs = [Request(prompt=[8, 4, 12], max_new_tokens=1,
                        arrival_time=0.0) for _ in range(8)]
        rset.run(reqs, clock=VirtualClock())
        assert int(rset.expert_replicas[0]) > 1      # expert 0 grew
        assert rset.hot_expert_events
        assert rset.hot_expert_events[0]["expert"] == 0
        # a cold expert did not replicate
        assert int(rset.expert_replicas[1]) == 1

    def test_expert_affinity_dispatch_spreads_hot_expert(self, model):
        from horovod_tpu.serve.replica import ReplicaSet

        cfg, params = model
        rset = ReplicaSet(cfg, params, self._page_cfg(cfg),
                          n_replicas=2, eos_id=1, moe_experts=4)
        assert rset._engine_set(0) == [0]
        rset.expert_replicas[0] = 2
        assert rset._engine_set(0) == [0, 1]

    def test_expert_load_rides_flight_dump(self, model, tmp_path):
        from horovod_tpu.monitor import flight as _flight
        from horovod_tpu.serve.engine import GenerationEngine
        from horovod_tpu.serve.engine import VirtualClock
        from horovod_tpu.serve.scheduler import Request

        cfg, params = model
        eng = GenerationEngine(cfg, params, self._page_cfg(cfg),
                               eos_id=1, moe_experts=4)
        eng.run([Request(prompt=[8, 3, 5], max_new_tokens=2,
                         arrival_time=0.0)], clock=VirtualClock())
        rec = _flight.recorder()
        dump = rec.build_dump("test")
        assert "expert_load" in dump
        assert sum(dump["expert_load"].values()) > 0
