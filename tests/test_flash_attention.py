"""Pallas flash attention vs the dense reference path.

Flash attention is an exact algorithm — forward AND backward (custom VJP
kernels) must match ``dense_attention`` to float tolerance. On the CPU
test mesh the kernels run in Pallas interpreter mode; the identical code
compiles through Mosaic on TPU (verified by bench.py --model gpt).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import GPT, gpt_tiny
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel import sequence as seqpar


def _qkv(B=1, T=128, H=2, D=32, seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(B, T, H, D), dtype) * 0.3
    return mk(), mk(), mk()


class TestFlashForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal)
        expect = seqpar.dense_attention(q, k, v, causal=causal)
        assert out.shape == q.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)

    def test_multi_block(self):
        """T spans several blocks (explicit block 128 < T) so the streaming
        softmax carry and the causal block-skip both execute."""
        q, k, v = _qkv(T=384, H=1, D=16, seed=3)
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
        expect = seqpar.dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)

    def test_block_shrinks_to_divisor(self):
        """T > preferred block and indivisible by it: block shrinks to the
        largest 128-multiple divisor instead of falling back to dense."""
        from horovod_tpu.ops.flash_attention import _pick_block

        assert _pick_block(768, 512) == 384
        assert _pick_block(100, 512) == 100    # single whole-seq block
        assert _pick_block(520, 512) is None   # no aligned divisor
        q, k, v = _qkv(T=768, H=1, D=16, seed=8)
        out = flash_attention(q, k, v, causal=True)
        expect = seqpar.dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16(self):
        q, k, v = _qkv(dtype=jnp.bfloat16, seed=1)
        out = flash_attention(q, k, v, causal=True)
        expect = seqpar.dense_attention(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_no_aligned_divisor_falls_back_to_dense(self):
        # 520 > 512 and has no 128-multiple divisor → dense path.
        q, k, v = _qkv(T=520, seed=2)
        out = flash_attention(q, k, v, causal=True)
        expect = seqpar.dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)

    def test_causal_cross_attention_rejected(self):
        q, _, _ = _qkv(T=128)
        _, k, v = _qkv(T=256)
        with pytest.raises(ValueError, match="Tq == Tk"):
            flash_attention(q, k, v, causal=True)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_dense(self, causal):
        q, k, v = _qkv(seed=4)
        w = jnp.asarray(np.random.RandomState(5).randn(32), jnp.float32)

        def loss(attn):
            return lambda q, k, v: jnp.sum(attn(q, k, v, causal=causal) * w)

        gf = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss(seqpar.dense_attention),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=f"d{name} mismatch")

    def test_grads_multi_block(self):
        q, k, v = _qkv(T=256, H=1, D=16, seed=6)

        def loss(attn):
            return lambda q, k, v: jnp.mean(
                attn(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss(lambda q, k, v, causal: flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128)),
            argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss(seqpar.dense_attention),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
                err_msg=f"d{name} mismatch")

    def test_grads_single_block(self):
        q, k, v = _qkv(T=256, H=1, D=16, seed=6)

        def loss(attn):
            return lambda q, k, v: jnp.mean(
                attn(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss(seqpar.dense_attention),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
                err_msg=f"d{name} mismatch")


class TestFlashRingAttention:
    """Sequence-parallel flash attention: ppermute ring of flash kernels
    with logsumexp partial merging; backward replays the ring with dk/dv
    accumulators traveling alongside their blocks."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        from horovod_tpu.ops.flash_attention import flash_ring_attention

        q, k, v = _qkv(T=256, H=2, D=16, seed=9)
        expect = seqpar.dense_attention(q, k, v, causal=causal)
        mesh = hvd.mesh()
        spec = P(None, hvd.HVD_AXES)
        out = jax.jit(hvd.shard_map(
            lambda a, b, c: flash_ring_attention(
                a, b, c, axis=hvd.HVD_AXES, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        ))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)

    def test_grads_match_dense(self):
        from horovod_tpu.ops.flash_attention import flash_ring_attention

        q, k, v = _qkv(T=256, H=2, D=16, seed=10)
        w = jnp.asarray(np.random.RandomState(11).randn(16), jnp.float32)
        mesh = hvd.mesh()
        spec = P(None, hvd.HVD_AXES)

        def ring_loss(q, k, v):
            o = hvd.shard_map(
                lambda a, b, c: flash_ring_attention(
                    a, b, c, axis=hvd.HVD_AXES, causal=True),
                mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec)(q, k, v)
            return jnp.sum(o * w)

        def dense_loss(q, k, v):
            return jnp.sum(seqpar.dense_attention(q, k, v, causal=True) * w)

        gf = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
        gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=f"d{name} mismatch")

    def test_gpt_flash_ring_matches_dense_gpt(self):
        cfg_d = gpt_tiny(dtype=jnp.float32)
        cfg_r = gpt_tiny(dtype=jnp.float32, attention="flash_ring",
                         seq_axis=hvd.HVD_AXES)
        B, T = 2, 64
        rs = np.random.RandomState(12)
        tokens = jnp.asarray(rs.randint(0, cfg_d.vocab_size, (B, T)))

        variables = GPT(cfg_d).init(jax.random.PRNGKey(0), tokens)
        expect = GPT(cfg_d).apply(variables, tokens)
        mesh = hvd.mesh()
        out = jax.jit(hvd.shard_map(
            lambda v, t: GPT(cfg_r).apply(v, t),
            mesh=mesh, in_specs=(P(), P(None, hvd.HVD_AXES)),
            out_specs=P(None, hvd.HVD_AXES),
        ))(variables, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=5e-4, atol=5e-4)


class TestFlashIntegration:
    def test_gpt_flash_matches_gpt_dense(self):
        cfg_d = gpt_tiny(dtype=jnp.float32)
        cfg_f = gpt_tiny(dtype=jnp.float32, attention="flash")
        B, T = 1, 128  # T = one full flash block → kernel path, not fallback
        rs = np.random.RandomState(0)
        tokens = jnp.asarray(rs.randint(0, cfg_d.vocab_size, (B, T)))

        variables = GPT(cfg_d).init(jax.random.PRNGKey(0), tokens)
        expect = GPT(cfg_d).apply(variables, tokens)
        out = GPT(cfg_f).apply(variables, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=5e-4, atol=5e-4)

    def test_ulysses_with_flash_local_attention(self):
        """Ulysses re-shards seq→heads; the local attention on the full
        gathered sequence runs the flash kernel inside shard_map."""
        q, k, v = _qkv(B=1, T=256, H=8, D=16, seed=7)
        expect = seqpar.dense_attention(q, k, v, causal=True)
        mesh = hvd.mesh()
        spec = P(None, hvd.HVD_AXES)
        out = jax.jit(hvd.shard_map(
            lambda a, b, c: seqpar.ulysses_attention(
                a, b, c, axis=hvd.HVD_AXES, causal=True,
                attn_fn=lambda qf, kf, vf: flash_attention(
                    qf, kf, vf, causal=True)),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        ))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)
