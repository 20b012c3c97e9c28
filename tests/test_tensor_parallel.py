"""Tensor parallelism: Megatron-style GPT sharding on the virtual mesh.

The tp model applied to sliced dense parameters must reproduce the dense
model exactly (column/row-parallel slicing + psum is a reorganization of
the same arithmetic), and DP x TP training must step with gradients
averaged over the data axis only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import GPT, gpt_tiny
from horovod_tpu.parallel.tensor import (
    tp_merge_params,
    tp_shard_params,
    tp_split_params,
    tp_unshard_params,
)


def _dense_and_tokens(B=2, T=32, seed=0, **over):
    cfg = gpt_tiny(dtype=jnp.float32, num_heads=8, d_model=64, d_ff=128,
                   **over)
    rs = np.random.RandomState(seed)
    tokens = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)))
    variables = GPT(cfg).init(jax.random.PRNGKey(0), tokens)
    return cfg, variables["params"], tokens


class TestTPShardParams:
    def test_roundtrip(self):
        _, params, _ = _dense_and_tokens()
        stacked = tp_shard_params(params, 4)
        back = tp_unshard_params(stacked)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6),
            params, back)

    def test_shard_shapes(self):
        _, params, _ = _dense_and_tokens()
        stacked = tp_shard_params(params, 8)
        qkv = stacked["h0"]["attn"]["qkv"]["kernel"]
        assert qkv.shape == (8, 64, 3 * 64 // 8)
        fc1 = stacked["h0"]["mlp"]["Dense_0"]["kernel"]
        assert fc1.shape == (8, 64, 128 // 8)
        fc2 = stacked["h0"]["mlp"]["Dense_1"]["kernel"]
        assert fc2.shape == (8, 128 // 8, 64)
        assert stacked["wte"].shape[0] == 8  # replicated copies


class TestTPGPT:
    def test_tp_overlapping_seq_axis_rejected(self):
        """Sequence-parallel attention on the same axis as tp would rotate
        k/v between different head shards — must fail loudly."""
        import dataclasses

        import pytest

        cfg, params, tokens = _dense_and_tokens()
        bad = dataclasses.replace(cfg, attention="ring",
                                  tp_axis=hvd.LOCAL_AXIS,
                                  seq_axis=hvd.LOCAL_AXIS)
        sharded, repl = tp_split_params(
            params, hvd.mesh().devices.shape[1])
        mesh = hvd.mesh()

        def spmd(stk, rp, tok):
            local = tp_merge_params(
                jax.tree.map(lambda a: a[0], stk), rp)
            return GPT(bad).apply({"params": local}, tok)

        with pytest.raises(ValueError, match="overlaps"):
            jax.jit(hvd.shard_map(
                spmd, mesh=mesh,
                in_specs=(P(hvd.LOCAL_AXIS), P(), P()),
                out_specs=P()))(sharded, repl, tokens)

    def test_tp8_matches_dense(self):
        """8-way TP over the full mesh == the dense model."""
        import dataclasses

        cfg, params, tokens = _dense_and_tokens()
        expect = GPT(cfg).apply({"params": params}, tokens)

        tp_cfg = dataclasses.replace(cfg, tp_axis=hvd.HVD_AXES)
        sharded, repl = tp_split_params(params, hvd.size())
        mesh = hvd.mesh()

        def spmd(stk, rp, tok):
            local = tp_merge_params(
                jax.tree.map(lambda a: a[0], stk), rp)
            return GPT(tp_cfg).apply({"params": local}, tok)

        out = jax.jit(hvd.shard_map(
            spmd, mesh=mesh, in_specs=(P(hvd.HVD_AXES), P(), P()),
            out_specs=P()))(sharded, repl, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)

    def test_dp_tp_2d(self):
        """DP over hvd_cross x TP over hvd_local: batch-sharded forward
        equals the dense model."""
        import dataclasses

        cfg, params, tokens = _dense_and_tokens(B=4)
        expect = GPT(cfg).apply({"params": params}, tokens)

        mesh = hvd.mesh()
        n_tp = mesh.devices.shape[1]
        tp_cfg = dataclasses.replace(cfg, tp_axis=hvd.LOCAL_AXIS)
        sharded, repl = tp_split_params(params, n_tp)

        def spmd(stk, rp, tok):
            local = tp_merge_params(
                jax.tree.map(lambda a: a[0], stk), rp)
            return GPT(tp_cfg).apply({"params": local}, tok)

        out = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(hvd.LOCAL_AXIS), P(), P(hvd.CROSS_AXIS)),
            out_specs=P(hvd.CROSS_AXIS)))(sharded, repl, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)

    def test_dp_tp_train_step(self):
        """One DP x TP training step: tp-sharded params update with
        gradients averaged over the DATA axis only."""
        import dataclasses

        cfg, params, tokens = _dense_and_tokens(B=4, seed=2)
        targets = jnp.asarray(
            np.random.RandomState(3).randint(0, cfg.vocab_size,
                                             tokens.shape))
        mesh = hvd.mesh()
        n_tp = mesh.devices.shape[1]
        tp_cfg = dataclasses.replace(cfg, tp_axis=hvd.LOCAL_AXIS)
        sharded, repl = tp_split_params(params, n_tp)
        # Gradient averaging over the dp (cross) axis ONLY — tp shards are
        # different parameters.
        tx = hvd.DistributedOptimizer(optax.adam(1e-3),
                                      axes=hvd.CROSS_AXIS)
        model = GPT(tp_cfg)

        def loss_fn(p, tok, tgt):
            logits = model.apply({"params": p}, tok)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()

        def spmd(stk, rp, tok, tgt):
            local = tp_merge_params(
                jax.tree.map(lambda a: a[0], stk), rp)
            opt_state = tx.init(local)
            loss, grads = hvd.value_and_grad(
                loss_fn, axes=hvd.CROSS_AXIS, reduce=False)(local, tok, tgt)
            updates, _ = tx.update(grads, opt_state, local)
            new_local = optax.apply_updates(local, updates)
            new_qkv = new_local["h0"]["attn"]["qkv"]["kernel"]
            return new_qkv[None], hvd.allreduce(loss)

        new_qkv, loss = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(hvd.LOCAL_AXIS), P(), P(hvd.CROSS_AXIS),
                      P(hvd.CROSS_AXIS)),
            out_specs=(P(hvd.LOCAL_AXIS), P())))(sharded, repl, tokens,
                                                 targets)
        assert np.isfinite(float(loss))
        # Parameters moved, and the qkv shards differ across tp ranks
        # (they are genuinely different parameters).
        q0 = np.asarray(new_qkv)
        assert not np.allclose(q0[0], np.asarray(
            sharded["h0"]["attn"]["qkv"]["kernel"][0]))
        assert not np.allclose(q0[0], q0[1])
