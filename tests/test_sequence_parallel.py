"""Sequence/context parallelism tests on the 8-device virtual mesh.

Ring attention and Ulysses all-to-all attention must be numerically exact
against dense attention over the full sequence (they are exact algorithms,
not approximations), including causal masking across shard boundaries.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import GPT, gpt_tiny
from horovod_tpu.parallel import sequence as seqpar


def _qkv(B=2, T=64, H=4, D=16, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(B, T, H, D), jnp.float32) * 0.3
    return mk(), mk(), mk()


def _shard_seq(fn, mesh, n_out=1):
    """Run fn inside shard_map with arrays sharded on seq dim over the full
    world (both mesh axes)."""
    spec = P(None, hvd.HVD_AXES)
    return jax.jit(hvd.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv()
        expect = seqpar.dense_attention(q, k, v, causal=causal)
        mesh = hvd.mesh()

        out = _shard_seq(
            lambda a, b, c: seqpar.ring_attention(
                a, b, c, axis=hvd.HVD_AXES, causal=causal),
            mesh)(q, k, v)
        assert out.shape == q.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)

    def test_local_axis_only(self):
        """Ring over just the intra-host (ICI) axis; batch stays whole."""
        q, k, v = _qkv(T=32)
        expect = seqpar.dense_attention(q, k, v, causal=True)
        mesh = hvd.mesh()
        spec = P(None, hvd.LOCAL_AXIS)
        out = jax.jit(hvd.shard_map(
            lambda a, b, c: seqpar.ring_attention(a, b, c,
                                                  axis=hvd.LOCAL_AXIS),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        ))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)

    def test_world_of_one_fallback(self):
        q, k, v = _qkv(T=16)
        out = seqpar.ring_attention(q, k, v, axis=())
        expect = seqpar.dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv(H=8)  # heads divisible by world (8)
        expect = seqpar.dense_attention(q, k, v, causal=causal)
        mesh = hvd.mesh()
        out = _shard_seq(
            lambda a, b, c: seqpar.ulysses_attention(
                a, b, c, axis=hvd.HVD_AXES, causal=causal),
            mesh)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)

    def test_indivisible_heads_rejected(self):
        q, k, v = _qkv(H=6)
        mesh = hvd.mesh()
        with pytest.raises(ValueError, match="divisible"):
            _shard_seq(
                lambda a, b, c: seqpar.ulysses_attention(
                    a, b, c, axis=hvd.HVD_AXES),
                mesh)(q, k, v)


class TestGPTSequenceParallel:
    def test_ring_gpt_matches_dense_gpt(self):
        """Full model forward: sequence-parallel GPT == single-device GPT."""
        cfg_d = gpt_tiny(dtype=jnp.float32)
        cfg_r = gpt_tiny(dtype=jnp.float32, attention="ring",
                         seq_axis=hvd.HVD_AXES)
        B, T = 2, 64
        rs = np.random.RandomState(0)
        tokens = jnp.asarray(rs.randint(0, cfg_d.vocab_size, (B, T)))

        model_d = GPT(cfg_d)
        variables = model_d.init(jax.random.PRNGKey(0), tokens)
        expect = model_d.apply(variables, tokens)

        model_r = GPT(cfg_r)
        mesh = hvd.mesh()
        out = jax.jit(hvd.shard_map(
            lambda v, t: model_r.apply(v, t),
            mesh=mesh, in_specs=(P(), P(None, hvd.HVD_AXES)),
            out_specs=P(None, hvd.HVD_AXES),
        ))(variables, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=5e-4, atol=5e-4)

    def test_ulysses_gpt_matches_dense_gpt(self):
        cfg_d = gpt_tiny(dtype=jnp.float32, num_heads=8, d_model=64)
        cfg_u = gpt_tiny(dtype=jnp.float32, num_heads=8, d_model=64,
                         attention="ulysses", seq_axis=hvd.HVD_AXES)
        B, T = 2, 64
        rs = np.random.RandomState(1)
        tokens = jnp.asarray(rs.randint(0, cfg_d.vocab_size, (B, T)))

        model_d = GPT(cfg_d)
        variables = model_d.init(jax.random.PRNGKey(0), tokens)
        expect = model_d.apply(variables, tokens)

        mesh = hvd.mesh()
        out = jax.jit(hvd.shard_map(
            lambda v, t: GPT(cfg_u).apply(v, t),
            mesh=mesh, in_specs=(P(), P(None, hvd.HVD_AXES)),
            out_specs=P(None, hvd.HVD_AXES),
        ))(variables, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=5e-4, atol=5e-4)

    def test_dp_sp_training_step(self):
        """2-D parallelism: data parallel over hvd_cross, sequence parallel
        over hvd_local — one full training step with the
        DistributedOptimizer (grads psum over the DP axis only)."""
        import optax

        cfg = gpt_tiny(dtype=jnp.float32, attention="ring",
                       seq_axis=hvd.LOCAL_AXIS, remat=True)
        mesh = hvd.mesh()
        n_dp = mesh.devices.shape[0]
        B, T = 2 * n_dp, 32
        rs = np.random.RandomState(2)
        tokens = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)))
        targets = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)))

        model = GPT(cfg)
        variables = model.init(jax.random.PRNGKey(0), tokens[:1])
        # Grads vary along BOTH axes (different batch shards over cross,
        # different token shards over local) → average over the full world.
        tx = hvd.DistributedOptimizer(optax.adam(1e-3))
        opt_state = tx.init(variables["params"])

        def loss_fn(params, tok, tgt):
            logits = model.apply({"params": params}, tok)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()

        def spmd(params, opt_state, tok, tgt):
            loss, grads = hvd.value_and_grad(
                loss_fn, reduce=False)(params, tok, tgt)
            updates, new_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            loss = hvd.allreduce(loss)
            return params, new_state, loss

        step = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(), P(hvd.CROSS_AXIS, hvd.LOCAL_AXIS),
                      P(hvd.CROSS_AXIS, hvd.LOCAL_AXIS)),
            out_specs=(P(), P(), P())))
        params, opt_state, loss = step(variables["params"], opt_state,
                                       tokens, targets)
        assert np.isfinite(float(loss))
        # one more step to ensure state threading works
        params, opt_state, loss2 = step(params, opt_state, tokens, targets)
        assert np.isfinite(float(loss2))
        assert float(loss2) < float(loss)
