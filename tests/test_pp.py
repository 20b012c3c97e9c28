"""Interleaved-1F1B pipeline parallelism (docs/pipeline.md).

The schedule family must be exact (or documented-ulp) against the dense
model through gradients, the send legs must validate/lower/account like
every other wire-plan leg, and the pp knobs must ride the autotune and
checkpoint machinery (schema v8; stage-count restore guard).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import GPT, gpt_tiny
from horovod_tpu.parallel.pipeline import (
    PPSchedule,
    _send_plan_for_axis,
    build_interleaved_schedule,
    pipelined_gpt_train,
    pp_split_chunks,
)
from horovod_tpu.plan import (
    PlanError,
    SEND,
    Leg,
    WirePlan,
    pp_bubble_bound,
    send_plan,
)


# ---------------------------------------------------------------------------
# IR: the send primitive.
# ---------------------------------------------------------------------------


class TestSendIR:
    def test_send_plan_encodes(self):
        p = send_plan("dcn", quantized=True, block=256,
                      error_feedback=True)
        assert p.encode() == "send:dcn.send[int8/256+ef]|s1|sync"
        assert send_plan("ici").encode() == "send:ici.send[payload]|s1|sync"

    def test_int8_on_ici_send_rejected(self):
        with pytest.raises(PlanError, match="non-DCN"):
            WirePlan("send", (Leg("ici", SEND, "int8", block=256),)
                     ).validate()

    def test_send_leg_outside_send_plan_rejected(self):
        with pytest.raises(PlanError, match="only belongs to a 'send'"):
            WirePlan("allreduce", (Leg("dcn", SEND),)).validate()

    def test_non_send_leg_inside_send_plan_rejected(self):
        with pytest.raises(PlanError, match="only send legs"):
            WirePlan("send", (Leg("dcn", "psum"),)).validate()

    def test_multi_leg_send_plan_rejected(self):
        with pytest.raises(PlanError, match="exactly ONE hop"):
            WirePlan("send", (Leg("dcn", SEND), Leg("ici", SEND))
                     ).validate()

    def test_flat_send_rejected(self):
        with pytest.raises(PlanError, match="LINK CLASS"):
            WirePlan("send", (Leg("flat", SEND),)).validate()

    def test_send_level_from_axis(self):
        assert _send_plan_for_axis(hvd.LOCAL_AXIS).legs[0].level == "ici"
        assert _send_plan_for_axis(hvd.HVD_AXES).legs[0].level == "dcn"
        # quantization is forced off on an ICI-class hop
        p = _send_plan_for_axis(hvd.LOCAL_AXIS, quantized=True)
        assert not p.is_quantized


# ---------------------------------------------------------------------------
# The schedule builder.
# ---------------------------------------------------------------------------


class TestSchedule:
    def test_units_complete_and_unique(self):
        M, n, v = 8, 4, 2
        s = build_interleaved_schedule(M, n, v)
        K = n * v
        # every (m, chunk) F and B lands exactly once, on its owner rank
        seen_f, seen_b = set(), set()
        for r in range(n):
            for t in range(s.ticks):
                if s.f_valid[r, t]:
                    c = s.f_j[r, t] * n + r
                    assert (s.f_m[r, t], c) not in seen_f
                    seen_f.add((s.f_m[r, t], c))
                if s.b_valid[r, t]:
                    c = s.b_j[r, t] * n + r
                    assert (s.b_m[r, t], c) not in seen_b
                    seen_b.add((s.b_m[r, t], c))
        assert seen_f == {(m, c) for m in range(M) for c in range(K)}
        assert seen_b == seen_f
        assert s.unit_count() == 2 * M * K

    def test_dependencies_respect_hop_latency(self):
        M, n, v = 8, 4, 2
        s = build_interleaved_schedule(M, n, v)
        K = n * v
        done_f, done_b = {}, {}
        for r in range(n):
            for t in range(s.ticks):
                if s.f_valid[r, t]:
                    done_f[(s.f_m[r, t], s.f_j[r, t] * n + r)] = t
                if s.b_valid[r, t]:
                    done_b[(s.b_m[r, t], s.b_j[r, t] * n + r)] = t
        for (m, c), t in done_f.items():
            if c > 0:
                assert done_f[(m, c - 1)] <= t - 1, (m, c)
        for (m, c), t in done_b.items():
            if c == K - 1:
                assert done_f[(m, c)] <= t - 1, (m, c)
            else:
                assert done_b[(m, c + 1)] <= t - 1, (m, c)

    def test_interleave_beats_gpipe_bound(self):
        # v = 1 (plain 1F1B) sits exactly AT the bound; v >= 2 beats it.
        for (M, n) in ((8, 4), (16, 4), (8, 2)):
            s1 = build_interleaved_schedule(M, n, 1)
            assert s1.bubble_fraction == pytest.approx(
                pp_bubble_bound(n, M), abs=1e-9)
            s2 = build_interleaved_schedule(M, n, 2)
            assert s2.bubble_fraction < pp_bubble_bound(n, M)
            # the Megatron interleaved bubble (S-1)/(Mv+S-1)
            assert s2.bubble_fraction == pytest.approx(
                (n - 1) / (M * 2 + n - 1), abs=1e-9)

    def test_microbatch_divisibility_enforced(self):
        with pytest.raises(ValueError, match="divisible"):
            build_interleaved_schedule(6, 4, 2)
        build_interleaved_schedule(6, 4, 1)  # v=1: any M is legal


# ---------------------------------------------------------------------------
# The zero-bubble family (zb1): B/W split + fill-tick capacity.
# ---------------------------------------------------------------------------


class TestZeroBubbleSchedule:
    def test_unknown_family_rejected(self):
        with pytest.raises(ValueError, match="unknown schedule family"):
            build_interleaved_schedule(8, 4, 1, family="zb2")

    def test_zb_units_complete_and_w_after_b(self):
        """Every (m, chunk) gets exactly one W unit, strictly after its
        B (dw consumes the grads B stashed), on the owner rank."""
        M, n, v = 8, 4, 2
        s = build_interleaved_schedule(M, n, v, family="zb1")
        K = n * v
        done_b, done_w = {}, {}
        for r in range(n):
            for t in range(s.ticks):
                assert not (s.f_valid[r, t] and s.w_valid[r, t])
                assert not (s.b_valid[r, t] and s.w_valid[r, t])
                if s.b_valid[r, t]:
                    done_b[(s.b_m[r, t], s.b_j[r, t] * n + r)] = t
                if s.w_valid[r, t]:
                    key = (s.w_m[r, t], s.w_j[r, t] * n + r)
                    assert key not in done_w, key
                    done_w[key] = t
        assert set(done_w) == {(m, c) for m in range(M)
                               for c in range(K)}
        for key, t in done_w.items():
            assert done_b[key] < t, key
        assert s.unit_count() == 3 * M * K
        assert s.units_per_rank == 3 * M * v

    def test_zb_bubble_strictly_below_1f1b(self):
        """The tentpole claim: on the same (S, M, v) the measured zb1
        bubble is strictly below the interleaved-1F1B bubble."""
        for (M, n, v) in ((8, 2, 1), (8, 4, 1), (16, 4, 4)):
            s1 = build_interleaved_schedule(M, n, v)
            sz = build_interleaved_schedule(M, n, v, family="zb1")
            assert sz.bubble_fraction < s1.bubble_fraction, (M, n, v)
            # and still below the GPipe bound, trivially
            assert sz.bubble_fraction < pp_bubble_bound(n, M)

    def test_zb_fill_ticks_enumerate_the_idle_grid(self):
        """fill_ticks[r, t] numbers rank r's idle ticks 0..cap-1 and is
        -1 on every busy tick — the T3 fill-capacity contract the
        ZeRO-3 flights are credited against (rank-uniform)."""
        for family in ("1f1b", "zb1"):
            s = build_interleaved_schedule(8, 4, 1, family=family)
            for r in range(s.stages):
                ks = []
                for t in range(s.ticks):
                    busy = bool(s.f_valid[r, t]) or bool(s.b_valid[r, t])
                    if s.w_valid is not None:
                        busy = busy or bool(s.w_valid[r, t])
                    if busy:
                        assert s.fill_ticks[r, t] == -1
                    else:
                        ks.append(int(s.fill_ticks[r, t]))
                assert ks == list(range(len(ks)))
                assert len(ks) == s.idle_ticks_per_rank


# ---------------------------------------------------------------------------
# Exactness: the schedule family vs the dense model, through gradients.
# ---------------------------------------------------------------------------


def _setup_gpt(L, B, T, seed):
    cfg = gpt_tiny(dtype=jnp.float32, num_layers=L)
    rs = np.random.RandomState(seed)
    tokens = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)))
    targets = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)))
    params = GPT(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    return cfg, params, tokens, targets


def _dense_ref(cfg, params, tokens, targets):
    def loss_fn(p):
        logits = GPT(cfg).apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()

    return jax.value_and_grad(loss_fn)(params)


class TestInterleavedParity:
    def _train(self, cfg, chunks, rest, tokens, targets, *, axis, n, v,
               M, schedule, send_plan_=None, dp_axes=None):
        mesh = hvd.mesh()

        def spmd(cp, rst, tok, tgt):
            local = jax.tree.map(lambda a: a[0], cp)
            loss, g_cp, g_rest = pipelined_gpt_train(
                cfg, local, rst, tok, tgt, axis=axis,
                num_microbatches=M, schedule=schedule, interleave=v,
                send_plan=send_plan_)
            if dp_axes:
                loss = hvd.allreduce(loss, op=hvd.Average, axes=dp_axes)
                g_cp = hvd.allreduce_pytree(g_cp, op=hvd.Average,
                                            axes=dp_axes)
                g_rest = hvd.allreduce_pytree(g_rest, op=hvd.Average,
                                              axes=dp_axes)
            return loss, jax.tree.map(lambda a: a[None], g_cp), g_rest

        in_data = P(dp_axes) if dp_axes else P()
        return jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(axis), P(), in_data, in_data),
            out_specs=(P(), P(axis), P())))(chunks, rest, tokens,
                                            targets)

    def test_interleaved_matches_dense_and_gpipe(self):
        """Interleaved-1F1B == 1F1B == GPipe == the dense model: loss
        and gradients (chunk blocks, tied embedding/head) within
        documented fp tolerance. DP over hvd_cross x PP over hvd_local
        — the 2-D composition users run at scale."""
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(2, 4))
            n, v, M = 4, 2, 4
            cfg, params, tokens, targets = _setup_gpt(
                L=n * v, B=2 * M, T=16, seed=0)
            want_loss, g_dense = _dense_ref(cfg, params, tokens, targets)
            chunks, rest = pp_split_chunks(params, n, v)
            chunks1, _ = pp_split_chunks(params, n, 1)

            results = {}
            for sched, cp, vv in (("gpipe", chunks1, 1),
                                  ("1f1b", chunks1, 1),
                                  ("interleaved_1f1b", chunks, v)):
                loss, g_cp, g_rest = self._train(
                    cfg, cp, rest, tokens, targets, axis=hvd.LOCAL_AXIS,
                    n=n, v=vv, M=M, schedule=sched,
                    dp_axes=hvd.CROSS_AXIS)
                results[sched] = (loss, g_cp, g_rest)
                np.testing.assert_allclose(float(loss), float(want_loss),
                                           rtol=3e-5)
                np.testing.assert_allclose(
                    np.asarray(g_rest["wte"]), np.asarray(g_dense["wte"]),
                    rtol=1e-3, atol=1e-6)

            # interleaved chunk grads == the dense per-block grads:
            # rank r's local chunk j is global chunk c = j*n + r.
            _, g_cp, _ = results["interleaved_1f1b"]
            for (r, j) in ((0, 0), (n - 1, v - 1)):
                got = jax.tree.map(lambda a: np.asarray(a[r, j, 0]), g_cp)
                want = jax.tree.map(np.asarray, g_dense[f"h{j * n + r}"])
                jax.tree.map(
                    lambda a, b: np.testing.assert_allclose(
                        a, b, rtol=1e-3, atol=1e-6), got, want)
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())

    def test_zb1_matches_dense(self):
        """zb1 == the dense model: the B/W split changes WHEN dw runs,
        never WHAT it computes — loss and per-block gradients at the
        same documented tolerance as interleaved-1F1B."""
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(2, 4))
            n, v, M = 4, 2, 4
            cfg, params, tokens, targets = _setup_gpt(
                L=n * v, B=2 * M, T=16, seed=4)
            want_loss, g_dense = _dense_ref(cfg, params, tokens, targets)
            chunks, rest = pp_split_chunks(params, n, v)
            loss, g_cp, g_rest = self._train(
                cfg, chunks, rest, tokens, targets, axis=hvd.LOCAL_AXIS,
                n=n, v=v, M=M, schedule="zb1", dp_axes=hvd.CROSS_AXIS)
            np.testing.assert_allclose(float(loss), float(want_loss),
                                       rtol=3e-5)
            np.testing.assert_allclose(
                np.asarray(g_rest["wte"]), np.asarray(g_dense["wte"]),
                rtol=1e-3, atol=1e-6)
            for (r, j) in ((0, 0), (n - 1, v - 1)):
                got = jax.tree.map(lambda a: np.asarray(a[r, j, 0]), g_cp)
                want = jax.tree.map(np.asarray, g_dense[f"h{j * n + r}"])
                jax.tree.map(
                    lambda a, b: np.testing.assert_allclose(
                        a, b, rtol=1e-3, atol=1e-6), got, want)
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())

    def test_quantized_ef_send_wire(self):
        """The int8+EF activation wire: loss within the blockwise
        quantization error bound of the exact wire (documented
        tolerance; the residual carries each hop's error forward)."""
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(2, 4))
            n, v, M = 4, 2, 4
            cfg, params, tokens, targets = _setup_gpt(
                L=n * v, B=2 * M, T=16, seed=1)
            want_loss, _ = _dense_ref(cfg, params, tokens, targets)
            chunks, rest = pp_split_chunks(params, n, v)
            # hvd_local is ICI-class; force a DCN-level plan to exercise
            # the quantized lowering (the wire, not the topology, is
            # under test).
            sp = send_plan("dcn", quantized=True, block=256,
                           error_feedback=True)
            loss, _, _ = self._train(
                cfg, chunks, rest, tokens, targets, axis=hvd.LOCAL_AXIS,
                n=n, v=v, M=M, schedule="interleaved_1f1b",
                send_plan_=sp, dp_axes=hvd.CROSS_AXIS)
            rel = abs(float(loss) - float(want_loss)) / abs(
                float(want_loss))
            assert rel < 1e-3, rel
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())


class TestPPMesh:
    """The dedicated hvd_pp mesh axis."""

    def test_pp_mesh_geometry(self):
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(2, 2),
                     pp_stages=2)
            assert hvd.pp_size() == 2
            assert hvd.pod_size() == 1
            assert hvd.data_mesh_shape() == (2, 2)
            assert hvd.mesh().axis_names == (hvd.PP_AXIS, hvd.CROSS_AXIS,
                                             hvd.LOCAL_AXIS)
            # data axes exclude the pp axis
            from horovod_tpu.common import basics

            assert basics.world_axes() == hvd.HVD_AXES
            assert "pp2" in basics.mesh_geometry()
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())

    def test_compose_zero2_on_pp_mesh(self):
        """pp x ZeRO-2: one pipelined SGD-momentum step on the hvd_pp
        mesh equals the dense single-device step (per-stage shard
        worlds = the data world)."""
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(1, 4),
                     pp_stages=2)
            mesh = hvd.mesh()
            n, v, M = 2, 2, 4
            cfg, params, tokens, targets = _setup_gpt(
                L=n * v, B=4 * M, T=8, seed=2)
            chunks, rest = pp_split_chunks(params, n, v)
            tx = hvd.DistributedOptimizer(
                optax.sgd(0.1, momentum=0.9), zero_stage=2,
                pp_stages=n, pp_microbatches=M,
                pp_schedule="interleaved_1f1b", pp_interleave=v)
            pspec = {"chunks": jax.tree.map(lambda _: P(hvd.PP_AXIS),
                                            chunks),
                     "rest": jax.tree.map(lambda _: P(), rest)}
            PPALL = (hvd.PP_AXIS,) + hvd.HVD_AXES
            sspec_of = lambda st: jax.tree.map(  # noqa: E731
                lambda l: P(PPALL) if getattr(l, "ndim", 0) >= 1
                else P(), st)
            state_tpl = tx.init(
                {"chunks": jax.tree.map(lambda a: a[0], chunks),
                 "rest": rest})

            def init_spmd(pt):
                return tx.init(
                    {"chunks": jax.tree.map(lambda a: a[0],
                                            pt["chunks"]),
                     "rest": pt["rest"]})

            ptree = {"chunks": chunks, "rest": rest}
            state = jax.jit(hvd.shard_map(
                init_spmd, mesh=mesh, in_specs=(pspec,),
                out_specs=sspec_of(state_tpl)))(ptree)
            sspec = sspec_of(state)

            def step_spmd(pt, st, tok, tgt):
                local_c = jax.tree.map(lambda a: a[0], pt["chunks"])
                loss, g_cp, g_rest = pipelined_gpt_train(
                    cfg, local_c, pt["rest"], tok, tgt,
                    axis=hvd.PP_AXIS, num_microbatches=M,
                    schedule="interleaved_1f1b", interleave=v)
                local = {"chunks": local_c, "rest": pt["rest"]}
                upd, st2 = tx.update({"chunks": g_cp, "rest": g_rest},
                                     st, local)
                new = optax.apply_updates(local, upd)
                loss = hvd.allreduce(loss, op=hvd.Average)
                # Re-establish the rest tree's pp replication by
                # construction (the buckets mixed pp-varying chunk
                # leaves into the gather; every stage holds the same
                # rest values).
                from jax import lax

                rpp = lax.axis_index(hvd.PP_AXIS)
                new_rest = jax.tree.map(
                    lambda a: lax.psum(
                        jnp.where(rpp == 0, a, jnp.zeros_like(a)),
                        hvd.PP_AXIS), new["rest"])
                return loss, {"chunks": jax.tree.map(
                    lambda a: a[None], new["chunks"]),
                    "rest": new_rest}, st2

            data = P(hvd.HVD_AXES)
            step = jax.jit(hvd.shard_map(
                step_spmd, mesh=mesh,
                in_specs=(pspec, sspec, data, data),
                out_specs=(P(), pspec, sspec)))
            loss, ptree, state = step(ptree, state, tokens, targets)

            # dense reference: one SGD-momentum step on the mean grads
            want_loss, g_dense = _dense_ref(cfg, params, tokens, targets)
            np.testing.assert_allclose(float(loss), float(want_loss),
                                       rtol=3e-5)
            ref_tx = optax.sgd(0.1, momentum=0.9)
            upd, _ = ref_tx.update(g_dense, ref_tx.init(params), params)
            want_p = optax.apply_updates(params, upd)
            got_rest = jax.device_get(ptree["rest"])
            np.testing.assert_allclose(
                np.asarray(got_rest["wte"]), np.asarray(want_p["wte"]),
                rtol=2e-4, atol=2e-6)
            # a chunk leaf: rank 0 chunk 0 == dense block h0
            got_c = jax.tree.map(lambda a: np.asarray(a[0, 0, 0]),
                                 jax.device_get(ptree["chunks"]))
            want_c = jax.tree.map(np.asarray, want_p["h0"])
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    a, b, rtol=2e-4, atol=2e-6), got_c, want_c)
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())

    def test_pp_knob_validation(self):
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(1, 4),
                     pp_stages=2)
            with pytest.raises(ValueError, match="disagrees with"):
                hvd.DistributedOptimizer(optax.sgd(0.1), pp_stages=4)
            with pytest.raises(ValueError, match="unknown pp_schedule"):
                hvd.DistributedOptimizer(optax.sgd(0.1), pp_stages=2,
                                         pp_schedule="zigzag")
            with pytest.raises(ValueError, match="divide"):
                hvd.DistributedOptimizer(
                    optax.sgd(0.1), pp_stages=2, pp_microbatches=5,
                    pp_interleave=2)
            # a legal composition builds
            hvd.DistributedOptimizer(optax.sgd(0.1), pp_stages=2,
                                     pp_microbatches=8, pp_interleave=2)
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())


# ---------------------------------------------------------------------------
# Accounting + spans.
# ---------------------------------------------------------------------------


class TestAccounting:
    def _trace_interleaved(self, send_plan_=None,
                           schedule="interleaved_1f1b"):
        n, v, M = 4, 2, 4
        cfg, params, tokens, targets = _setup_gpt(L=n * v, B=2 * M, T=8,
                                                  seed=3)
        chunks, rest = pp_split_chunks(params, n, v)
        mesh = hvd.mesh()

        def spmd(cp, rst, tok, tgt):
            local = jax.tree.map(lambda a: a[0], cp)
            loss, g_cp, g_rest = pipelined_gpt_train(
                cfg, local, rst, tok, tgt, axis=hvd.LOCAL_AXIS,
                num_microbatches=M, schedule=schedule,
                interleave=v, send_plan=send_plan_)
            loss = hvd.allreduce(loss, op=hvd.Average,
                                 axes=hvd.CROSS_AXIS)
            return loss

        f = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(hvd.LOCAL_AXIS), P(), P(hvd.CROSS_AXIS),
                      P(hvd.CROSS_AXIS)),
            out_specs=P()))
        with hvd.record_wire_stats() as ws:
            f.lower(chunks, rest, tokens, targets)
        return ws, n, v, M, cfg, tokens

    def test_send_bytes_accounted(self):
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(2, 4))
            ws, n, v, M, cfg, tokens = self._trace_interleaved()
            sched = build_interleaved_schedule(M, n, v)
            # per-tick cyclic hops: one activation (payload dtype) + one
            # grad (f32) per rank, repeats = ticks; the per-shard
            # microbatch is [B/(M*dp_cross), T, C].
            mb = (tokens.shape[0] // (M * 2)) * tokens.shape[1] \
                * cfg.d_model
            want = 2 * sched.ticks * mb * 4.0
            assert ws.pp_bytes == pytest.approx(want)
            assert ws.pp_sends == 2 * sched.ticks
            # send bytes also land on their link-class totals
            assert ws.ici_bytes >= ws.pp_bytes

        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())

    def test_pp_spans_balanced(self, tmp_path):
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(2, 4))
            path = str(tmp_path / "pp_tl.json")
            hvd.start_timeline(path)
            try:
                self._trace_interleaved()
            finally:
                hvd.stop_timeline()
            events = json.load(open(path))
            from horovod_tpu.monitor.span_audit import audit_spans

            # strict=: every event in the trace must come from the
            # CHECKED vocabulary table (span_audit.KNOWN_PREFIXES) — a
            # typo'd span family fails here, not in a skewed report.
            audit = audit_spans(events, prefix="PP:", require_spans=True,
                                strict=True)
            assert audit.balanced
            sched = build_interleaved_schedule(4, 4, 2)
            busy = audit.count.get("PP:F", 0) + audit.count.get("PP:B", 0)
            assert busy == sched.unit_count()
            assert audit.count.get("PP:SEND", 0) == 2  # one per direction
            assert audit.instants.get("PP:SCHEDULE", 0) == 1
            bubble = 1.0 - busy / float(sched.stages * sched.ticks)
            assert bubble == pytest.approx(sched.bubble_fraction)
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())

    def test_zb_spans_count_w_units(self, tmp_path):
        """Under zb1 the W units show up as PP:W spans and the measured
        busy fraction reproduces the (smaller) zb bubble — the same
        span-derived bubble bench.py reports."""
        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(2, 4))
            path = str(tmp_path / "zb_tl.json")
            hvd.start_timeline(path)
            try:
                self._trace_interleaved(schedule="zb1")
            finally:
                hvd.stop_timeline()
            events = json.load(open(path))
            from horovod_tpu.monitor.span_audit import audit_spans

            audit = audit_spans(events, prefix="PP:", require_spans=True,
                                strict=True)
            assert audit.balanced
            sched = build_interleaved_schedule(4, 4, 2, family="zb1")
            assert audit.count.get("PP:W", 0) == \
                sched.microbatches * sched.interleave * sched.stages
            busy = (audit.count.get("PP:F", 0)
                    + audit.count.get("PP:B", 0)
                    + audit.count.get("PP:W", 0))
            assert busy == sched.unit_count()
            bubble = 1.0 - busy / float(sched.stages * sched.ticks)
            assert bubble == pytest.approx(sched.bubble_fraction)
            ref = build_interleaved_schedule(4, 4, 2)
            assert bubble < ref.bubble_fraction
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())

    def test_bubble_fill_credits_streamed_gathers(self):
        """A zero3_gather_params trace under fill_sched= credits one
        idle tick per streamed bucket flight, capped at the schedule's
        per-rank fill capacity; without the window nothing is
        credited."""
        params = {f"w{i}": jnp.ones((1024,), jnp.float32)
                  for i in range(6)}
        tpl = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        psh = hvd.zero3_shard_params(params,
                                     fusion_threshold_bytes=4096)
        pspec = hvd.zero3_param_pspecs(psh)
        n_buckets = len(jax.tree.leaves(psh))
        sched = build_interleaved_schedule(8, 4, 1, family="zb1")
        cap = sched.idle_ticks_per_rank
        assert 0 < cap < n_buckets  # the capacity cap is exercised

        def run(fill):
            def spmd(psh):
                p = hvd.zero3_gather_params(
                    psh, tpl, fusion_threshold_bytes=4096,
                    overlap=True, num_comm_streams=2, fill_sched=fill)
                return jax.tree.map(lambda a: a.sum(), p)

            f = jax.jit(hvd.shard_map(
                spmd, mesh=hvd.mesh(), in_specs=(pspec,),
                out_specs=jax.tree.map(lambda _: P(), tpl)))
            with hvd.record_wire_stats() as ws:
                f.lower(psh)
            return ws

        ws = run(sched)
        assert ws.filled_ticks == cap
        assert ws.bubble_hidden_bytes > 0
        # a filled flight is still overlap-scheduled — never double
        # freedom: hidden-in-bubble bytes are a subset of overlap bytes
        assert ws.bubble_hidden_bytes <= ws.overlap_bytes
        ws0 = run(None)
        assert ws0.filled_ticks == 0
        assert ws0.bubble_hidden_bytes == 0.0
        assert ws0.overlap_bytes == ws.overlap_bytes


# ---------------------------------------------------------------------------
# Golden --dump-plan table: the send legs are pinned text.
# ---------------------------------------------------------------------------


class TestGoldenPlan:
    def test_dump_plan_pins_send_leg(self):
        sp = hvd.describe_plan(mesh_shape=(2, 2), pp_stages=4,
                               pp_microbatches=8, pp_interleave=2,
                               pp_quantized=True, quantized=False,
                               zero_stage=0, overlap=False,
                               hierarchical=False, num_comm_streams=1,
                               quant_block=256,
                               fusion_threshold_bytes=64 * 1024 * 1024,
                               quantized_pod=False)
        table = sp.table(payload_bytes=4 * 1024 * 1024)
        assert ("send               1 dcn   send           int8/256   "
                "yes xla          0") in table
        assert ("pp: stages=4 interleave=2 microbatches=8 "
                "schedule=interleaved_1f1b gpipe_bubble_bound=0.2727 "
                "(send rows priced per issue, docs/pipeline.md)") in table
        assert sp.encode() == (
            "allreduce:flat.psum[payload]|s1|sync + "
            "pp4v2m8.interleaved_1f1b@send:dcn.send[int8/256+ef]|s1|sync")

    def test_ici_hop_never_quantizes(self):
        sp = hvd.describe_plan(mesh_shape=(1, 4), pp_stages=2,
                               pp_quantized=True, quantized=False,
                               zero_stage=0, overlap=False,
                               hierarchical=False)
        assert sp.send.legs[0].level == "ici"
        assert not sp.send.is_quantized


# ---------------------------------------------------------------------------
# Autotune schema v8.
# ---------------------------------------------------------------------------


class TestAutotuneV8:
    def test_encode_decode_pp_segment(self):
        from horovod_tpu.autotune.parameter_manager import TunedParams
        from horovod_tpu.plan.planner import decode_tuned, encode_tuned

        p = TunedParams(pp_microbatches=16, pp_interleave=2)
        enc = encode_tuned(p, pp=True)
        assert enc == "ar.flat|fp|s1|sync|pp16/2"
        d = decode_tuned(enc)
        assert d["pp_microbatches"] == 16 and d["pp_interleave"] == 2
        # pp off: the segment (and both knobs) drop out — dead knobs
        # never split trials
        assert encode_tuned(p) == "ar.flat|fp|s1|sync"
        d0 = decode_tuned(encode_tuned(p))
        assert d0["pp_microbatches"] == 0 and d0["pp_interleave"] == 1

    def test_manager_canonicalizes_dead_pp_knobs(self):
        from horovod_tpu.autotune.parameter_manager import (
            ParameterManager, TunedParams)

        pm = ParameterManager(TunedParams(), warmup_samples=0,
                              max_samples=3, tune_pp=False)
        c = pm._canonicalize(TunedParams(pp_microbatches=16,
                                         pp_interleave=4))
        assert c.pp_microbatches == 0 and c.pp_interleave == 1

    def test_manager_snaps_pp_proposals(self):
        from horovod_tpu.autotune.parameter_manager import (
            ParameterManager, TunedParams)

        pm = ParameterManager(TunedParams(pp_microbatches=8,
                                          pp_interleave=2),
                              warmup_samples=0, max_samples=8,
                              tune_pp=True, pp_stages=3,
                              pp_max_interleave=2)
        for u6 in (0.0, 0.33, 0.7, 1.0):
            p = pm._from_unit((0.5, 0.5, 0.25, 0.25, 0.25, 0.0, u6, 1.0))
            assert p.pp_microbatches % 3 == 0
            assert p.pp_microbatches >= 3
            assert p.pp_interleave <= 2

    def test_csv_roundtrip_with_pp_columns(self, tmp_path):
        from horovod_tpu.autotune.parameter_manager import (
            CSV_FIELDS, ParameterManager, TunedParams, read_log)

        assert "pp_microbatches" in CSV_FIELDS
        assert "pp_interleave" in CSV_FIELDS
        path = str(tmp_path / "log.csv")
        pm = ParameterManager(TunedParams(pp_microbatches=8,
                                          pp_interleave=2),
                              warmup_samples=0, max_samples=3,
                              tune_pp=True, pp_stages=4,
                              pp_max_interleave=2, log_path=path)
        while not pm.done:
            pm.record_sample(1.0)
        rows = read_log(path)
        assert rows and all("pp_microbatches" in r for r in rows)
        assert rows[0]["pp_microbatches"] == 8
        assert rows[0]["pp_interleave"] == 2
        assert rows[0]["plan"].endswith("|pp8/2")

    def test_read_log_tolerant_of_v7_csv(self, tmp_path):
        from horovod_tpu.autotune.parameter_manager import read_log

        path = tmp_path / "v7.csv"
        path.write_text(
            "sample,fusion_threshold_bytes,quant_block,"
            "hierarchical_allreduce,zero_sharding,zero_stage,overlap,"
            "num_comm_streams,fused,score_steps_per_sec,plan\n"
            "1,4194304,256,0,0,0,0,1,0,12.5,ar.flat|fp|s1|sync\n")
        rows = read_log(str(path))
        assert rows[0]["pp_microbatches"] == 0
        assert rows[0]["pp_interleave"] == 1

    def test_tuned_params_from_v7_dict(self):
        from horovod_tpu.autotune.parameter_manager import TunedParams

        p = TunedParams.from_dict({
            "fusion_threshold_bytes": 4 << 20, "quant_block": 256,
            "hierarchical_allreduce": False, "zero_stage": 2,
            "overlap": True, "num_comm_streams": 2, "fused": False})
        assert p.pp_microbatches == 0 and p.pp_interleave == 1

    def test_shortlist_prices_pp_candidates(self):
        from horovod_tpu.plan.planner import shortlist

        rows = shortlist(8 * 1024 * 1024, mesh_shape=(2, 2),
                         tune_pp=True, pp_stages=4, pp_max_interleave=2,
                         tune_hierarchical=False, k=6)
        assert rows
        ppms = {r.params.pp_microbatches for r in rows}
        assert len(ppms) > 1  # distinct pp candidates priced + ranked
        for r in rows:
            assert r.plan.send is not None
            assert r.cost.pp_ms > 0


# ---------------------------------------------------------------------------
# Autotune schema v11: the pp_schedule knob (zero-bubble family).
# ---------------------------------------------------------------------------


class TestAutotuneV11:
    def test_encode_decode_zb_segment(self):
        from horovod_tpu.autotune.parameter_manager import TunedParams
        from horovod_tpu.plan.planner import decode_tuned, encode_tuned

        p = TunedParams(pp_microbatches=8, pp_interleave=2,
                        pp_schedule="zb1")
        enc = encode_tuned(p, pp=True)
        assert enc == "ar.flat|fp|s1|sync|pp8/2|zb1"
        d = decode_tuned(enc)
        assert d["pp_schedule"] == "zb1"
        assert d["pp_microbatches"] == 8 and d["pp_interleave"] == 2
        # the segment is optional: every v10 encoding is a valid v11
        # encoding and decodes to the exact pre-v11 default
        d10 = decode_tuned("ar.flat|fp|s1|sync|pp8/2")
        assert d10["pp_schedule"] == "interleaved_1f1b"
        # pp off: schedule rides the pp group, so it drops with it
        assert encode_tuned(p) == "ar.flat|fp|s1|sync"
        assert decode_tuned("ar.flat|fp|s1|sync")["pp_schedule"] == \
            "interleaved_1f1b"

    def test_manager_canonicalizes_dead_zb_knob(self):
        from horovod_tpu.autotune.parameter_manager import (
            ParameterManager, TunedParams)

        pm = ParameterManager(TunedParams(), warmup_samples=0,
                              max_samples=3, tune_pp=False)
        c = pm._canonicalize(TunedParams(pp_microbatches=16,
                                         pp_interleave=2,
                                         pp_schedule="zb1"))
        # zb1 is meaningless without a pipeline: collapses with the
        # other pp knobs so equal plans dedup as ONE trial
        assert c.pp_schedule == "interleaved_1f1b"
        assert c.pp_microbatches == 0 and c.pp_interleave == 1

    def test_unit_cube_roundtrip_and_v10_tuple_tolerance(self):
        from horovod_tpu.autotune.parameter_manager import (
            ParameterManager, TunedParams)

        pm = ParameterManager(TunedParams(pp_microbatches=8),
                              warmup_samples=0, max_samples=8,
                              tune_pp=True, pp_stages=4,
                              pp_max_interleave=1)
        for u12, want in ((0.0, "interleaved_1f1b"),
                          (0.25, "interleaved_1f1b"),
                          (0.75, "zb1"), (1.0, "zb1")):
            p = pm._from_unit((0.5, 0.5, 0.25, 0.25, 0.25, 0.0,
                               0.5, 0.0, 0.25, 0.25, 0.25, 0.25, u12))
            assert p.pp_schedule == want
            # round trip: _to_unit lands the same side of 0.5
            back = pm._from_unit(pm._to_unit(p))
            assert back.pp_schedule == want
        # pre-v11 unit tuples (len < 13) still resolve — the zb dim
        # was appended at the tail precisely so old coordinates stay
        # valid, defaulting to the pre-v11 schedule
        p8 = pm._from_unit((0.5, 0.5, 0.25, 0.25, 0.25, 0.0, 0.5, 0.0))
        assert p8.pp_schedule == "interleaved_1f1b"

    def test_csv_roundtrip_with_pp_schedule_column(self, tmp_path):
        from horovod_tpu.autotune.parameter_manager import (
            CSV_FIELDS, ParameterManager, TunedParams, read_log)

        assert "pp_schedule" in CSV_FIELDS
        path = str(tmp_path / "log.csv")
        pm = ParameterManager(TunedParams(pp_microbatches=8,
                                          pp_schedule="zb1"),
                              warmup_samples=0, max_samples=3,
                              tune_pp=True, pp_stages=4,
                              pp_max_interleave=1, log_path=path)
        while not pm.done:
            pm.record_sample(1.0)
        rows = read_log(path)
        assert rows and all("pp_schedule" in r for r in rows)
        assert rows[0]["pp_schedule"] == "zb1"
        assert rows[0]["plan"].endswith("|zb1")

    def test_read_log_tolerant_of_v10_csv(self, tmp_path):
        from horovod_tpu.autotune.parameter_manager import read_log

        # A v10-era log: no pp_schedule column — reads cleanly and
        # defaults to the exact pre-v11 schedule.
        path = tmp_path / "v10.csv"
        path.write_text(
            "sample,fusion_threshold_bytes,quant_block,"
            "hierarchical_allreduce,zero_sharding,zero_stage,overlap,"
            "num_comm_streams,fused,pp_microbatches,pp_interleave,"
            "moe_capacity_factor,moe_quantized,spec_draft_k,"
            "kv_migrate_quantized,score_steps_per_sec,plan\n"
            "1,4194304,256,0,0,0,0,1,0,8,2,0.0,0,0,0,12.5,"
            "ar.flat|fp|s1|sync|pp8/2\n")
        rows = read_log(str(path))
        assert rows[0]["pp_schedule"] == "interleaved_1f1b"
        assert rows[0]["pp_microbatches"] == 8

    def test_tuned_params_from_v10_dict(self):
        from horovod_tpu.autotune.parameter_manager import TunedParams

        p = TunedParams.from_dict({
            "fusion_threshold_bytes": 4 << 20, "quant_block": 256,
            "hierarchical_allreduce": False, "zero_stage": 2,
            "overlap": True, "num_comm_streams": 2,
            "pp_microbatches": 8, "pp_interleave": 2})
        assert p.pp_schedule == "interleaved_1f1b"
        rt = TunedParams.from_dict(p.as_dict())
        assert rt == p

    def test_enumerate_offers_both_schedules_under_tune_pp(self):
        from horovod_tpu.plan.planner import enumerate_tuned

        cands = enumerate_tuned(tune_pp=True, pp_stages=4,
                                pp_max_interleave=1)
        scheds = {p.pp_schedule for p in cands}
        assert scheds == {"interleaved_1f1b", "zb1"}
        # tune_pp off: the schedule stays pinned — no phantom trials
        pinned = {p.pp_schedule for p in enumerate_tuned()}
        assert pinned == {"interleaved_1f1b"}


# ---------------------------------------------------------------------------
# Checkpoint ride-along: stage-count guard + same-stage round-trip.
# ---------------------------------------------------------------------------


class TestCheckpointGuard:
    def test_stage_count_change_fails_loudly(self, tmp_path):
        from horovod_tpu import checkpoint as hvd_ckpt

        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(1, 4),
                     pp_stages=2)
            mgr = hvd_ckpt.CheckpointManager(str(tmp_path), keep=2)
            state = hvd_ckpt.CheckpointedJaxState(
                mgr, params=jnp.arange(8.0), step=0)
            state.step = 3
            state.commit()
            assert state.wait(30)
            mgr.close()
        finally:
            hvd.shutdown()
        try:
            hvd.init(devices=jax.devices())  # 1-stage (no pp) mesh
            mgr = hvd_ckpt.CheckpointManager(str(tmp_path), keep=2)
            with pytest.raises(ValueError,
                               match="2-stage pipeline mesh"):
                hvd_ckpt.CheckpointedJaxState(
                    mgr, params=jnp.arange(8.0), step=0)
            mgr.close()
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())

    def test_same_stage_roundtrip_bit_identical(self, tmp_path):
        from horovod_tpu import checkpoint as hvd_ckpt

        hvd.shutdown()
        try:
            hvd.init(devices=jax.devices(), mesh_shape=(1, 4),
                     pp_stages=2)
            vals = jnp.asarray(
                np.random.RandomState(0).randn(16).astype(np.float32))
            mgr = hvd_ckpt.CheckpointManager(str(tmp_path), keep=2)
            state = hvd_ckpt.CheckpointedJaxState(mgr, params=vals,
                                                  step=0)
            state.step = 5
            state.commit()
            assert state.wait(30)
            mgr.close()
            hvd.shutdown()
            hvd.init(devices=jax.devices(), mesh_shape=(1, 4),
                     pp_stages=2)
            mgr = hvd_ckpt.CheckpointManager(str(tmp_path), keep=2)
            restored = hvd_ckpt.CheckpointedJaxState(
                mgr, params=jnp.zeros(16), step=0)
            assert restored.restored_from == 5
            assert restored.step == 5
            np.testing.assert_array_equal(np.asarray(restored.params),
                                          np.asarray(vals))
            mgr.close()
        finally:
            hvd.shutdown()
            hvd.init(devices=jax.devices())
