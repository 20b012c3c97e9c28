"""Expert parallelism: Switch-MoE with all-to-all dispatch on the mesh.

With a generous capacity (no overflow drops) the EP-sharded layer is
EXACT against the world-1 all-experts-local computation: buffering and
the two all-to-alls are a reorganization of the same per-token FFN.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import GPT, gpt_tiny
from horovod_tpu.parallel.expert import (
    SwitchMoE,
    ep_split_params,
    switch_moe,
    switch_moe_ragged,
)
from horovod_tpu.parallel.tensor import tp_merge_params


def _layer_data(N=64, C=16, F=32, E=8, seed=0):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(N, C), jnp.float32) * 0.5
    router = jnp.asarray(rs.randn(C, E), jnp.float32) * 0.3
    w1 = jnp.asarray(rs.randn(E, C, F), jnp.float32) * 0.1
    b1 = jnp.asarray(rs.randn(E, F), jnp.float32) * 0.01
    w2 = jnp.asarray(rs.randn(E, F, C), jnp.float32) * 0.1
    b2 = jnp.asarray(rs.randn(E, C), jnp.float32) * 0.01
    return x, router, w1, b1, w2, b2


class TestSwitchMoE:
    def test_matches_per_token_ffn(self):
        """No-drop regime: y_i == gate_i * FFN_{e_i}(x_i) exactly."""
        x, router, w1, b1, w2, b2 = _layer_data()
        y, aux = switch_moe(x, router, w1, b1, w2, b2,
                            capacity_factor=8.0)
        probs = jax.nn.softmax(x @ router)
        e = jnp.argmax(probs, axis=-1)
        gate = jnp.take_along_axis(probs, e[:, None], axis=-1)[:, 0]
        import flax.linen as nn

        h = nn.gelu(jnp.einsum("nc,ncf->nf", x, w1[e]) + b1[e])
        expect = (jnp.einsum("nf,nfc->nc", h, w2[e]) + b2[e]) * gate[:, None]
        np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)
        assert float(aux) > 0

    def test_capacity_drops_overflow(self):
        """capacity_factor ~0 forces drops: dropped tokens emit zeros."""
        x, router, w1, b1, w2, b2 = _layer_data(N=32, E=4)
        y, _ = switch_moe(x, router, w1, b1, w2, b2,
                          capacity_factor=0.125)  # capacity 1/expert
        # At most E tokens (one per expert) can be non-zero.
        nonzero = np.count_nonzero(
            np.abs(np.asarray(y)).sum(axis=-1) > 1e-9)
        assert nonzero <= 4

    def test_ep_sharded_matches_local(self):
        """8-way EP over the mesh == all-experts-local (no drops)."""
        x, router, w1, b1, w2, b2 = _layer_data()
        expect, aux_e = switch_moe(x, router, w1, b1, w2, b2,
                                   capacity_factor=8.0)
        mesh = hvd.mesh()
        n = hvd.size()

        def spmd(x, router, w1s, b1s, w2s, b2s):
            y, aux = switch_moe(
                x, router, w1s[0], b1s[0], w2s[0], b2s[0],
                axis=hvd.HVD_AXES, capacity_factor=8.0)
            # y is identical on every rank (same tokens everywhere) but
            # vma cannot prove it — emit stacked per-rank copies.
            return y[None], hvd.allreduce(aux, op=hvd.Average)

        stack = lambda a: jnp.stack(jnp.split(a, n, axis=0))
        y, aux = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(), P(hvd.HVD_AXES), P(hvd.HVD_AXES),
                      P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(hvd.HVD_AXES), P())))(
            x, router, stack(w1), stack(b1), stack(w2), stack(b2))
        for r in range(n):   # every rank's copy equals the local reference
            np.testing.assert_allclose(np.asarray(y[r]), np.asarray(expect),
                                       rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(aux), float(aux_e), rtol=1e-5)

    def test_expert_count_must_divide(self):
        x, router, w1, b1, w2, b2 = _layer_data(E=8)
        with pytest.raises(ValueError, match="experts"):
            # Router says 8 experts but locals x axis = 8 * 8 = 64.
            jax.jit(hvd.shard_map(
                lambda x, r, a, b, c, d: switch_moe(
                    x, r, a, b, c, d, axis=hvd.HVD_AXES)[0],
                mesh=hvd.mesh(),
                in_specs=(P(), P(), P(), P(), P(), P()),
                out_specs=P()))(x, router, w1, b1, w2, b2)


def _per_token_expect(x, router, w1, b1, w2, b2):
    import flax.linen as nn

    probs = jax.nn.softmax(x @ router)
    e = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, e[:, None], axis=-1)[:, 0]
    h = nn.gelu(jnp.einsum("nc,ncf->nf", x, w1[e]) + b1[e])
    return (jnp.einsum("nf,nfc->nc", h, w2[e]) + b2[e]) * gate[:, None]


class TestSwitchMoERagged:
    def test_matches_per_token_ffn_world1(self):
        x, router, w1, b1, w2, b2 = _layer_data()
        y, aux = switch_moe_ragged(x, router, w1, b1, w2, b2,
                                   capacity_factor=8.0)
        expect = _per_token_expect(x, router, w1, b1, w2, b2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)
        assert float(aux) > 0

    def test_ep_sharded_per_rank_tokens(self):
        """8-way EP, DIFFERENT tokens per rank, no drops: exact against
        the per-token FFN on every rank's own tokens."""
        n = hvd.size()
        Np, C, F, E = 8, 16, 32, 8
        rs = np.random.RandomState(3)
        x_all = jnp.asarray(rs.randn(n * Np, C), jnp.float32) * 0.5
        _, router, w1, b1, w2, b2 = _layer_data(C=C, F=F, E=E, seed=3)

        def spmd(x, router, w1s, b1s, w2s, b2s):
            y, aux = switch_moe_ragged(
                x, router, w1s[0], b1s[0], w2s[0], b2s[0],
                axis=hvd.HVD_AXES, capacity_factor=8.0,
                pair_capacity_factor=8.0)
            return y, hvd.allreduce(aux, op=hvd.Average)

        stack = lambda a: jnp.stack(jnp.split(a, n, axis=0))
        y, _ = jax.jit(hvd.shard_map(
            spmd, mesh=hvd.mesh(),
            in_specs=(P(hvd.HVD_AXES), P(), P(hvd.HVD_AXES),
                      P(hvd.HVD_AXES), P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(hvd.HVD_AXES), P())))(
            x_all, router, stack(w1), stack(b1), stack(w2), stack(b2))
        expect = _per_token_expect(x_all, router, w1, b1, w2, b2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                                   rtol=1e-4, atol=1e-5)

    def test_ragged_pools_capacity_fixed_drops(self):
        """Sender-skewed routing: the fixed path's per-(sender, expert)
        quota drops tokens the ragged pooled capacity keeps."""
        n = hvd.size()
        Np, C = 8, 8
        E, F = 8, 16
        # Router ~ 10*I with C == E: token one_hot(e) routes to expert e.
        router = jnp.eye(C, E) * 10.0
        rs = np.random.RandomState(4)
        w1 = jnp.asarray(rs.randn(E, C, F), jnp.float32) * 0.1
        b1 = jnp.asarray(rs.randn(E, F), jnp.float32) * 0.01
        w2 = jnp.asarray(rs.randn(E, F, C), jnp.float32) * 0.1
        b2 = jnp.asarray(rs.randn(E, C), jnp.float32) * 0.01
        # Rank 0's tokens ALL route to expert 0; rank r>0's tokens to
        # expert r. Global expert-0 load (8) == pooled cap at cf=1.0
        # (N*n/E = 8), but blows the per-sender quota (N*cf/E = 1).
        dest_e = np.zeros((n, Np), np.int64)
        for r in range(1, n):
            dest_e[r, :] = r
        x_all = jnp.asarray(np.eye(C)[dest_e.reshape(-1)], jnp.float32)

        def run(moe_fn, **kw):
            def spmd(x, router, w1s, b1s, w2s, b2s):
                y, _ = moe_fn(x, router, w1s[0], b1s[0], w2s[0], b2s[0],
                              axis=hvd.HVD_AXES, capacity_factor=1.0, **kw)
                return y

            stack = lambda a: jnp.stack(jnp.split(a, n, axis=0))
            return np.asarray(jax.jit(hvd.shard_map(
                spmd, mesh=hvd.mesh(),
                in_specs=(P(hvd.HVD_AXES), P(), P(hvd.HVD_AXES),
                          P(hvd.HVD_AXES), P(hvd.HVD_AXES),
                          P(hvd.HVD_AXES)),
                out_specs=P(hvd.HVD_AXES)))(
                x_all, router, stack(w1), stack(b1), stack(w2), stack(b2)))

        y_fixed = run(switch_moe)
        y_ragged = run(switch_moe_ragged, pair_capacity_factor=8.0)
        # Fixed: rank 0 keeps only 1 of its 8 expert-0 tokens.
        rank0_fixed = np.abs(y_fixed[:Np]).sum(-1)
        assert np.count_nonzero(rank0_fixed > 1e-9) == 1
        # Ragged: pooled capacity keeps all of them — exact everywhere.
        expect = np.asarray(_per_token_expect(x_all, router, w1, b1, w2, b2))
        np.testing.assert_allclose(y_ragged, expect, rtol=1e-4, atol=1e-5)

    def test_ragged_gradients_match_dense_no_drop(self):
        """d(loss)/d(params) through the ragged dispatch == world-1."""
        n = hvd.size()
        Np, C, F, E = 4, 8, 16, 8
        rs = np.random.RandomState(5)
        x_all = jnp.asarray(rs.randn(n * Np, C), jnp.float32) * 0.5
        _, router, w1, b1, w2, b2 = _layer_data(C=C, F=F, E=E, seed=5)

        def loss_world1(w1, w2):
            y, _ = switch_moe_ragged(x_all, router, w1, b1, w2, b2,
                                     capacity_factor=8.0)
            return jnp.sum(y * y)

        g1 = jax.grad(loss_world1, argnums=(0, 1))(w1, w2)

        def loss_spmd(x, w1s, b1s, w2s, b2s):
            def inner(w1r, w2r):
                y, _ = switch_moe_ragged(
                    x, router, w1r, b1s[0], w2r, b2s[0],
                    axis=hvd.HVD_AXES, capacity_factor=8.0,
                    pair_capacity_factor=8.0)
                return jax.lax.psum(jnp.sum(y * y), hvd.HVD_AXES)

            return jax.grad(inner, argnums=(0, 1))(w1s[0], w2s[0])

        stack = lambda a: jnp.stack(jnp.split(a, n, axis=0))
        g8 = jax.jit(hvd.shard_map(
            loss_spmd, mesh=hvd.mesh(),
            in_specs=(P(hvd.HVD_AXES), P(hvd.HVD_AXES), P(hvd.HVD_AXES),
                      P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(hvd.HVD_AXES), P(hvd.HVD_AXES))))(
            x_all, stack(w1), stack(b1), stack(w2), stack(b2))
        np.testing.assert_allclose(np.asarray(g8[0]).reshape(w1.shape),
                                   np.asarray(g1[0]), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g8[1]).reshape(w2.shape),
                                   np.asarray(g1[1]), rtol=1e-4, atol=1e-5)


class TestMoEGPT:
    def test_moe_gpt_trains(self):
        """World-1 MoE GPT: loss decreases with router aux loss mixed in."""
        cfg = gpt_tiny(dtype=jnp.float32, moe_experts=4)
        B, T = 4, 32
        rs = np.random.RandomState(0)
        toks = rs.randint(0, cfg.vocab_size, (B, T + 1))
        x, y = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
        model = GPT(cfg)
        variables = model.init(jax.random.PRNGKey(0), x)
        tx = optax.adam(1e-2)
        opt = tx.init(variables["params"])

        @jax.jit
        def step(p, s):
            def loss_fn(p):
                logits, inter = model.apply(
                    {"params": p}, x, mutable=["intermediates"])
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()
                aux = sum(jax.tree.leaves(inter["intermediates"]))
                return loss + 0.01 * aux
            loss, g = jax.value_and_grad(loss_fn)(p)
            u, s = tx.update(g, s, p)
            return optax.apply_updates(p, u), s, loss

        params = variables["params"]
        losses = []
        for _ in range(8):
            params, opt, loss = step(params, opt)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_dp_ep_moe_trains_with_aux_balancing(self):
        """End-to-end DP x EP TRAINING step: the router's sown aux losses
        are collected (``mutable=['intermediates']``) and mixed into the
        objective, so load balancing has gradient effect in the
        distributed wiring too — the pattern users should copy (advisor
        r3: no training path retrieved the sown aux)."""
        cfg = gpt_tiny(dtype=jnp.float32, moe_experts=8,
                       moe_capacity_factor=8.0)
        B, T = 4, 16
        rs = np.random.RandomState(2)
        toks = rs.randint(0, cfg.vocab_size, (B, T + 1))
        x, y = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
        variables = GPT(cfg).init(jax.random.PRNGKey(0), x)
        mesh = hvd.mesh()
        n_ep = mesh.devices.shape[1]
        ep_cfg = dataclasses.replace(cfg, ep_axis=hvd.LOCAL_AXIS)
        sharded, repl = ep_split_params(variables["params"], n_ep)

        def spmd(stk, rp, tok, tgt):
            def loss_fn(stk, rp):
                local = tp_merge_params(
                    jax.tree.map(lambda a: a[0], stk), rp)
                logits, inter = GPT(ep_cfg).apply(
                    {"params": local}, tok, mutable=["intermediates"])
                task = optax.softmax_cross_entropy_with_integer_labels(
                    logits, tgt).mean()
                aux = sum(jax.tree.leaves(inter["intermediates"]))
                return task + 0.01 * aux, aux

            (loss, aux), (g_stk, g_rp) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(stk, rp)
            # Replicated params (router included): grads averaged over the
            # whole mesh. Expert shards live on one ep rank each: average
            # over the data axis only.
            g_rp = hvd.allreduce_pytree(g_rp, op=hvd.Average)
            g_stk = hvd.allreduce_pytree(g_stk, op=hvd.Average,
                                         axes=hvd.CROSS_AXIS)
            stk = jax.tree.map(lambda p, g: p - 0.05 * g, stk, g_stk)
            rp = jax.tree.map(lambda p, g: p - 0.05 * g, rp, g_rp)
            return (stk, rp, hvd.allreduce(loss, op=hvd.Average),
                    hvd.allreduce(aux, op=hvd.Average))

        step = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(hvd.LOCAL_AXIS), P(), P(hvd.CROSS_AXIS),
                      P(hvd.CROSS_AXIS)),
            out_specs=(P(hvd.LOCAL_AXIS), P(), P(), P())))
        losses, auxes = [], []
        for _ in range(6):
            sharded, repl, loss, aux = step(sharded, repl, x, y)
            losses.append(float(loss))
            auxes.append(float(aux))
        assert losses[-1] < losses[0], losses
        assert all(np.isfinite(a) and a > 0 for a in auxes), auxes

    def test_dp_ep_gpt_matches_dense_params(self):
        """DP over cross x EP over local: forward equals the world-1 MoE
        model on the same (sliced) parameters."""
        cfg = gpt_tiny(dtype=jnp.float32, moe_experts=8,
                       moe_capacity_factor=8.0)
        B, T = 4, 16
        rs = np.random.RandomState(1)
        tokens = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)))
        variables = GPT(cfg).init(jax.random.PRNGKey(0), tokens)
        expect = GPT(cfg).apply(variables, tokens)

        mesh = hvd.mesh()
        n_ep = mesh.devices.shape[1]
        ep_cfg = dataclasses.replace(cfg, ep_axis=hvd.LOCAL_AXIS)
        sharded, repl = ep_split_params(variables["params"], n_ep)

        def spmd(stk, rp, tok):
            local = tp_merge_params(
                jax.tree.map(lambda a: a[0], stk), rp)
            logits = GPT(ep_cfg).apply({"params": local}, tok)
            # Identical across the ep axis in value (every rank holds the
            # full combined output) but not provably so — stack copies.
            return logits[None]

        out = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(hvd.LOCAL_AXIS), P(), P(hvd.CROSS_AXIS)),
            out_specs=P(hvd.LOCAL_AXIS, hvd.CROSS_AXIS)))(
            sharded, repl, tokens)
        for r in range(n_ep):
            np.testing.assert_allclose(np.asarray(out[r]),
                                       np.asarray(expect),
                                       rtol=2e-4, atol=2e-4)
