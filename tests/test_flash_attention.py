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
from horovod_tpu.monitor.registry import counter
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


def _fwd_and_grads(attn, q, k, v, w):
    o = attn(q, k, v)
    grads = jax.grad(lambda q, k, v: jnp.sum(
        attn(q, k, v).astype(jnp.float32) * w), argnums=(0, 1, 2))(q, k, v)
    return (o, *grads)


class TestInCellTiling:
    """The kernels walk (256, 256) sub-tiles of a grid cell that the
    diagonal crosses: future sub-tiles are never computed, only those it
    crosses are masked; any other cell is one tile. Forward and gradients
    against ``dense_attention``."""

    @pytest.mark.parametrize("T,causal,blocks,dtype,tol", [
        (1024, True, (None, None), jnp.float32, 5e-6),   # the cells' case:
        #   one whole-sequence block, 10 of its 16 sub-tiles, static bounds
        (1024, True, (512, 512), jnp.float32, 5e-6),     # bounds from the
        #   program ids: diagonal grid cells skip inside too
        (1024, True, (512, 1024), jnp.float32, 5e-6),    # bq != bk
        (1024, True, (1024, 512), jnp.float32, 5e-6),
        (512, True, (256, 512), jnp.float32, 5e-6),
        (384, True, (None, None), jnp.float32, 5e-6),    # 3 x 3 of 128
        (640, True, (None, None), jnp.float32, 5e-6),    # 5 x 5 of 128
        (200, True, (None, None), jnp.float32, 5e-6),    # no aligned
        #   divisor: one sub-tile the size of the block
        (512, False, (None, None), jnp.float32, 5e-6),   # every sub-tile
        (1024, False, (512, 256), jnp.float32, 5e-6),
        (512, True, (None, None), jnp.bfloat16, 2e-2),
    ])
    def test_matches_dense(self, T, causal, blocks, dtype, tol):
        q, k, v = _qkv(T=T, H=1, D=16, seed=T, dtype=dtype)
        w = jnp.asarray(np.random.RandomState(5).randn(16), jnp.float32)
        got = _fwd_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=blocks[0], block_k=blocks[1]),
            q, k, v, w)
        want = _fwd_and_grads(lambda q, k, v: seqpar.dense_attention(
            q, k, v, causal=causal), q, k, v, w)
        for a, b, name in zip(got, want, ("o", "dq", "dk", "dv")):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.abs(a - b).max() <= tol * np.abs(b).max(), name

    def test_multi_block_inside_shard_map(self):
        """Sub-tile loops whose bounds come from the program ids, under
        shard_map's varying-axes checking (batch sharded)."""
        q, k, v = _qkv(B=8, T=512, H=1, D=16, seed=21)
        spec = P(hvd.HVD_AXES)

        def loss(q, k, v):
            o = hvd.shard_map(
                lambda a, b, c: flash_attention(
                    a, b, c, causal=True, block_q=256, block_k=256),
                mesh=hvd.mesh(), in_specs=(spec,) * 3, out_specs=spec,
            )(q, k, v)
            return jnp.sum(o * o)

        gf = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gd = jax.grad(lambda q, k, v: jnp.sum(seqpar.dense_attention(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
                err_msg=f"d{name} mismatch")

    @pytest.mark.parametrize("q_off,k_off", [(0, 256), (512, 0), (256, 256),
                                             (128, 384)])
    def test_ring_partial_with_offsets(self, q_off, k_off):
        """A ring partial (runtime offsets, ``static_skip=False``) runs
        every sub-tile under the global-position mask; a row whose keys
        all lie in its future gives o = 0 and lse = -1e30, and no
        gradient."""
        from horovod_tpu.ops import flash_attention as F

        T, D = 512, 16
        rs = np.random.RandomState(q_off + k_off)
        q, k, v, do = (jnp.asarray(rs.randn(1, T, D), jnp.float32) * 0.3
                       for _ in range(4))
        scale = D ** -0.5
        visible = (q_off + np.arange(T))[:, None] >= (
            k_off + np.arange(T))[None, :]
        seen = visible.any(axis=1)

        def dense(q, k, v):
            s = jnp.where(visible, jnp.einsum("btd,bsd->bts", q, k) * scale,
                          -1e30)
            p = jnp.where(visible, jax.nn.softmax(s, axis=-1), 0.0)
            return (jnp.einsum("bts,bsd->btd", p, v),
                    jax.nn.logsumexp(s, axis=-1))

        o, lse = F._flash_fwd(q, k, v, scale, True, T, T,
                              q_off=jnp.int32(q_off), k_off=jnp.int32(k_off),
                              static_skip=False)
        o_d, lse_d = dense(q, k, v)
        np.testing.assert_allclose(np.asarray(o)[0, seen],
                                   np.asarray(o_d)[0, seen],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(lse)[0, 0, seen],
                                   np.asarray(lse_d)[0, seen],
                                   rtol=1e-5, atol=1e-6)
        assert not np.asarray(o)[0, ~seen].any()
        assert (np.asarray(lse)[0, 0, ~seen] == -1e30).all()

        delta = F._prep_residuals(o, do)
        dq = F._flash_bwd_dq(q, k, v, do, lse, delta, scale, True, T, T,
                             q_off=jnp.int32(q_off), k_off=jnp.int32(k_off),
                             static_skip=False)
        dk, dv = F._flash_bwd_dkv(q, k, v, do, lse, delta, scale, True, T,
                                  T, q_off=jnp.int32(q_off),
                                  k_off=jnp.int32(k_off), static_skip=False)
        want = jax.grad(lambda q, k, v: jnp.sum(dense(q, k, v)[0] * do),
                        argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip((dq, dk, dv), want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
                err_msg=f"d{name} mismatch")

    def test_tile_counters(self):
        """``flash.tiles_{total,computed,masked}{kernel=...}`` count, at
        trace time and per head, the sub-tiles of a kernel call."""
        from horovod_tpu.monitor.registry import counter
        from horovod_tpu.ops import flash_attention as F

        def read():
            return {(n, kern): counter(f"flash.tiles_{n}", kernel=kern).value
                    for n in ("total", "computed", "masked")
                    for kern in ("fwd", "bwd_dq", "bwd_dkv")}

        def traced(T, bq, bk, **kw):
            x = jax.ShapeDtypeStruct((2, T, 64), jnp.bfloat16)
            r = jax.ShapeDtypeStruct((2, 1, T), jnp.float32)
            before = read()
            jax.eval_shape(lambda q: F._flash_fwd(
                q, q, q, 0.125, True, bq, bk, **kw), x)
            for fn in (F._flash_bwd_dq, F._flash_bwd_dkv):
                jax.eval_shape(lambda q, r: fn(
                    q, q, q, q, r, r, 0.125, True, bq, bk, **kw), x, r)
            after = read()
            return {key: after[key] - before[key] for key in after}

        for case, want in (
                (traced(1024, 1024, 1024), (16, 10, 4)),   # the cells' shape
                # A 2 x 2 grid: the two cells on the diagonal skip inside
                # (3 of 4 sub-tiles, 2 masked), the one below is one tile,
                # the one above never runs (counted as 4).
                (traced(1024, 512, 512), (13, 7, 4)),
                # bq != bk: cells (0, 0), (1, 2) skip inside (7 of 8,
                # 2 masked); (0, 1), (1, 3) are crossed off the sub-tile
                # lattice and run as one masked tile; two run whole, two
                # never.
                (traced(2048, 1024, 512), (36, 18, 6)),
                (traced(1024, 1024, 1024, q_off=jnp.int32(1024),
                        k_off=jnp.int32(0), static_skip=False),
                 (1, 1, 1))):                              # a ring partial
            for kern in ("fwd", "bwd_dq", "bwd_dkv"):
                assert tuple(case[(n, kern)] for n in (
                    "total", "computed", "masked")) == want, (kern, case)


def _layout_counts():
    from horovod_tpu.monitor.registry import counter

    return {path: counter("flash.layout", path=path).value
            for path in ("in_place", "packed")}


def _assert_close(got, want, tol):
    for a, b, name in zip(got, want, ("o", "dq", "dk", "dv")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), name


def _primitives(jaxpr):
    """Names of every primitive in a jaxpr, sub-jaxprs included (the
    kernels' own bodies are one ``pallas_call`` each: what runs inside a
    kernel is not layout traffic)."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _primitives(sub)
    return names


class TestInPlaceLayout:
    """Where whole heads fill whole 128-lane blocks (D = 64 with an even
    head count, D = 128), the kernels index ``[B, T, H * D]`` as the
    projections give it: no transpose around a call, the heads of a lane
    block take turns on a grid axis. Any other shape is packed to
    ``[B * H, T, D]`` as before. Same numbers either way."""

    @pytest.mark.parametrize("H,D", [(16, 64), (12, 64), (4, 128)])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("T,blocks", [
        (256, (None, None)),    # one block: a head finishes inside a cell
        (384, (128, 128)),      # 3 x 3 blocks: carried across k blocks,
        #   and a lane block's k/v come once per head of it
        (512, (256, 128)),      # bq != bk
    ])
    def test_matches_dense(self, H, D, causal, T, blocks):
        from horovod_tpu.ops.flash_attention import _reads_in_place

        assert _reads_in_place(H, D)
        q, k, v = _qkv(B=2 if T == 256 else 1, T=T, H=H, D=D, seed=T + H)
        w = jnp.asarray(np.random.RandomState(5).randn(H, D), jnp.float32)
        before = _layout_counts()
        got = _fwd_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=blocks[0], block_k=blocks[1]),
            q, k, v, w)
        after = _layout_counts()
        # _fwd_and_grads traces the public function twice.
        assert after["in_place"] - before["in_place"] == 2
        assert after["packed"] == before["packed"]
        want = _fwd_and_grads(lambda q, k, v: seqpar.dense_attention(
            q, k, v, causal=causal), q, k, v, w)
        _assert_close(got, want, 5e-6)

    @pytest.mark.parametrize("H,D", [(16, 64), (4, 128)])
    def test_bf16(self, H, D):
        q, k, v = _qkv(T=256, H=H, D=D, seed=H, dtype=jnp.bfloat16)
        w = jnp.asarray(np.random.RandomState(5).randn(H, D), jnp.float32)
        got = _fwd_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True), q, k, v, w)
        want = _fwd_and_grads(lambda q, k, v: seqpar.dense_attention(
            q, k, v, causal=True), q, k, v, w)
        assert all(a.dtype == jnp.bfloat16 for a in got)
        _assert_close(got, want, 2e-2)

    @pytest.mark.parametrize("H,D", [(3, 64), (2, 80), (2, 32)])
    def test_other_shapes_stay_packed(self, H, D):
        """An odd head count at D = 64, a head dim that does not divide
        128, a row narrower than a lane block: today's path, one count a
        call on its side, the same numbers."""
        from horovod_tpu.ops.flash_attention import _reads_in_place

        assert not _reads_in_place(H, D)
        q, k, v = _qkv(T=256, H=H, D=D, seed=D)
        w = jnp.asarray(np.random.RandomState(5).randn(H, D), jnp.float32)
        before = _layout_counts()
        got = _fwd_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True), q, k, v, w)
        after = _layout_counts()
        assert after["packed"] - before["packed"] == 2
        assert after["in_place"] == before["in_place"]
        want = _fwd_and_grads(lambda q, k, v: seqpar.dense_attention(
            q, k, v, causal=True), q, k, v, w)
        _assert_close(got, want, 5e-6)

    @pytest.mark.parametrize("H,D,in_place", [
        (16, 64, True), (12, 64, True), (4, 128, True), (3, 64, False)])
    def test_no_transpose_around_an_in_place_call(self, H, D, in_place):
        """The jaxpr of ``flash_attention`` and of its gradient: three
        kernels and, for an in-place shape, no ``transpose`` primitive
        (the packed path has its eight)."""
        x = jax.ShapeDtypeStruct((2, 256, H, D), jnp.bfloat16)

        def grads(q, k, v):
            return jax.grad(lambda q, k, v: flash_attention(
                q, k, v, causal=True).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)

        fwd = _primitives(jax.make_jaxpr(lambda q, k, v: flash_attention(
            q, k, v, causal=True))(x, x, x).jaxpr)
        both = _primitives(jax.make_jaxpr(grads)(x, x, x).jaxpr)
        assert fwd.count("pallas_call") == 1
        assert both.count("pallas_call") == 3
        assert fwd.count("transpose") == (0 if in_place else 4)
        assert both.count("transpose") == (0 if in_place else 8)

    def test_inside_shard_map(self):
        """The benchmark's step calls it under ``hvd.shard_map`` (batch
        sharded): varying-axes checking over the five-axis grid."""
        q, k, v = _qkv(B=8, T=256, H=2, D=64, seed=31)
        spec = P(hvd.HVD_AXES)

        def loss(q, k, v):
            o = hvd.shard_map(
                lambda a, b, c: flash_attention(a, b, c, causal=True),
                mesh=hvd.mesh(), in_specs=(spec,) * 3, out_specs=spec,
            )(q, k, v)
            return jnp.sum(o * o)

        gf = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gd = jax.grad(lambda q, k, v: jnp.sum(seqpar.dense_attention(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
                err_msg=f"d{name} mismatch")

    def test_tile_counters_at_the_cells_shape(self):
        """``flash.tiles_*`` count one head's grid whatever the layout: 16
        sub-tiles, 10 computed, 4 masked at T = 1024, as before."""
        from horovod_tpu.monitor.registry import counter

        def read():
            return {(n, kern): counter(f"flash.tiles_{n}", kernel=kern).value
                    for n in ("total", "computed", "masked")
                    for kern in ("fwd", "bwd_dq", "bwd_dkv")}

        x = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16)
        before = read()
        jax.eval_shape(lambda q, k, v: jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=1024,
                block_k=1024).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v), x, x, x)
        after = read()
        for kern in ("fwd", "bwd_dq", "bwd_dkv"):
            assert tuple(after[(n, kern)] - before[(n, kern)] for n in (
                "total", "computed", "masked")) == (16, 10, 4), kern

    def test_gpt_in_place_matches_gpt_dense(self):
        """The model's call site: 2 heads of 64 (one lane block) through
        ``attention="flash"``, logits and parameter gradients against the
        dense model's."""
        cfg_d = gpt_tiny(dtype=jnp.float32, d_model=128, num_heads=2)
        cfg_f = gpt_tiny(dtype=jnp.float32, d_model=128, num_heads=2,
                         attention="flash")
        rs = np.random.RandomState(0)
        tokens = jnp.asarray(rs.randint(0, cfg_d.vocab_size, (2, 128)))
        variables = GPT(cfg_d).init(jax.random.PRNGKey(0), tokens)

        def loss(cfg):
            return lambda v: jnp.mean(GPT(cfg).apply(v, tokens) ** 2)

        before = _layout_counts()
        lf, gf = jax.value_and_grad(loss(cfg_f))(variables)
        assert _layout_counts()["in_place"] > before["in_place"]
        ld, gd = jax.value_and_grad(loss(cfg_d))(variables)
        np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(gf),
                        jax.tree_util.tree_leaves(gd)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=1e-6)


def _seen(T, *, causal=True, window=None, block=None, q_off=0, k_off=0):
    """[T, T] bool: which (query, key) pairs a call sees, from the masks'
    definitions."""
    from horovod_tpu.ops.flash_attention import block_diffusion_mask

    if block is not None:
        return np.asarray(block_diffusion_mask(T // 2, block))
    ahead = (q_off + np.arange(T))[:, None] - (k_off + np.arange(T))[None, :]
    seen = ahead >= 0 if causal else np.ones((T, T), bool)
    if window is not None:
        seen &= ahead < window
    return seen


def _dense_o_lse(q, k, v, seen):
    """q [B, T, H, D], k / v [B, T, Hkv, D] -> (o [B, T, H, D], lse
    [B * H, T]): plain attention and its log-sum-exp under ``seen``."""
    B, T, H, D = q.shape
    k, v = (jnp.repeat(x, H // k.shape[2], axis=2) for x in (k, v))
    s = jnp.where(seen, jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5,
                  -1e30)
    p = jnp.where(seen, jax.nn.softmax(s, axis=-1), 0.0)
    return (jnp.einsum("bhqk,bkhd->bqhd", p, v),
            jax.nn.logsumexp(s, axis=-1).reshape(B * H, T))


def _dense_window(q, k, v, window):
    """Plain attention with grouped KV heads and a window: query t sees
    keys t - window + 1 .. t."""
    return _dense_o_lse(q, k, v, _seen(q.shape[1], window=window))[0]


def _grouped_qkv(T, H, Hkv, D, seed=0):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(1, T, n, D), jnp.float32)
                 for n in (H, Hkv, Hkv, H))


class TestWindowAndGroupedHeads:
    """``flash_attention(window=, k/v with fewer heads)`` against dense
    attention: forward and all three gradients, in both layouts
    (docs/flash_window.md)."""

    T, BLOCK = 256, 128

    @pytest.mark.parametrize("layout", ["by_shape", "packed"])
    @pytest.mark.parametrize("D", [64, 128])
    @pytest.mark.parametrize("group", [1, 8])
    @pytest.mark.parametrize("window", [None, 128, 77, 256, 300])
    def test_matches_dense(self, window, group, D, layout, monkeypatch):
        from horovod_tpu.ops import flash_attention as F

        H = 8
        if layout == "packed":
            monkeypatch.setattr(F, "_reads_in_place", lambda H, D: False)
        q, k, v, w = _grouped_qkv(self.T, H, H // group, D)
        before = _layout_counts()
        got = _fwd_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, block_q=self.BLOCK,
            block_k=self.BLOCK), q, k, v, w)
        want = _fwd_and_grads(
            lambda q, k, v: _dense_window(q, k, v, window), q, k, v, w)
        _assert_close(got, want, 2e-4)
        assert got[2].shape == k.shape and got[3].shape == v.shape
        in_place = layout == "by_shape" and (group == 1 or D == 128)
        after = _layout_counts()
        assert (after["in_place"] > before["in_place"]) == in_place
        assert (after["packed"] > before["packed"]) == (not in_place)

    def test_a_window_the_sequence_fits_in_is_no_window(self):
        """``window >= T`` compiles the causal kernels: no ``_win`` name in
        the jaxpr, and the windowed counters stay where they were."""
        q, k, v, _ = _grouped_qkv(128, 2, 2, 64)
        text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
            q, k, v, window=128))(q, k, v))
        assert "hvd_flash_fwd" in text and "_win" not in text
        text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
            q, k, v, window=127))(q, k, v))
        assert "hvd_flash_fwd_win" in text

    @pytest.mark.parametrize("bad", [dict(window=0), dict(window=8,
                                                          causal=False)])
    def test_rejects_a_window_that_is_none(self, bad):
        q, k, v, _ = _grouped_qkv(128, 2, 2, 64)
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, k, v, **bad)

    def test_rejects_heads_that_do_not_share_evenly(self):
        q, k, v, _ = _grouped_qkv(128, 6, 4, 64)
        with pytest.raises(ValueError, match="share"):
            flash_attention(q, k, v)

    def test_kv_group_is_counted(self):
        before = counter("flash.kv_group").value
        q, k, v, _ = _grouped_qkv(128, 8, 2, 64)
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v), q, k, v)
        assert counter("flash.kv_group").value - before == 4

    @pytest.mark.parametrize("window", [1, 100, 128, 200, 257, 384, 511])
    @pytest.mark.parametrize("block", [128, 256, 512])
    def test_tile_counters_are_the_bands(self, window, block, monkeypatch):
        """``flash.tiles_*{window=}`` of a windowed call against a brute
        force count over the positions: the cells of the grid are the
        blocks the band touches, a cell wholly inside the band is one
        tile, any other is cut into sub-tiles of which those with a
        visible pair are computed and those with an invisible one too are
        masked."""
        from horovod_tpu.ops import flash_attention as F

        monkeypatch.setattr(F, "_SUB_TILE", (128, 128))
        T, sub = 512, 128
        ahead = np.arange(T)[:, None] - np.arange(T)[None, :]
        seen = (ahead >= 0) & (ahead < window)
        n = T // block
        total = computed = masked = 0
        for i in range(n):
            for j in range(n):
                cell = seen[i * block:(i + 1) * block,
                            j * block:(j + 1) * block]
                edge = block if cell.all() else sub
                tiles = [cell[a:a + edge, c:c + edge]
                         for a in range(0, block, edge)
                         for c in range(0, block, edge)]
                total += len(tiles)
                computed += sum(t.any() for t in tiles)
                masked += sum(t.any() and not t.all() for t in tiles)

        def counts():
            return {(name, kern): counter(
                f"flash.tiles_{name}", kernel=kern,
                window=str(window)).value
                for name in ("total", "computed", "masked")
                for kern in ("fwd", "bwd_dq", "bwd_dkv")}

        before = counts()
        q, k, v, _ = _grouped_qkv(T, 2, 1, 64)
        jax.eval_shape(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, window=window, block_q=block,
            block_k=block).sum(), argnums=(0, 1, 2)), q, k, v)
        got = {key: counts()[key] - before[key] for key in before}
        for kern in ("fwd", "bwd_dq", "bwd_dkv"):
            assert (got["total", kern], got["computed", kern],
                    got["masked", kern]) == (total, computed, masked), kern

    def test_no_block_past_the_window_is_in_the_grid(self):
        """The k axis of a windowed call's grid spans the band's blocks
        alone: 2 of the 4 at T = 512, window 128, blocks of 128."""
        from horovod_tpu.ops import flash_attention as F

        q, k, v, _ = _grouped_qkv(512, 2, 1, 128)
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, window=128, block_q=128, block_k=128).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        grids = {}

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    grids[eqn.params["name"]] = tuple(
                        eqn.params["grid_mapping"].grid)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        assert F._band_blocks(128, 128, 4) == 2
        assert grids["hvd_flash_fwd_win"] == (1, 2, 4, 1, 2)
        assert grids["hvd_flash_bwd_dq_win"] == (1, 2, 4, 1, 2)
        # The dk/dv kernel's second axis counts KV heads, its fourth the
        # query heads of a group: dk, dv are summed over them in scratch.
        assert grids["hvd_flash_bwd_dkv_win"] == (1, 1, 4, 2, 2)


# (T, H, Hkv, D, bq, bk, layout, the call's mask). What each walks:
_WALKS = {
    # one (512, 512) cell a head, T = bq: two strips, the second a
    # (256, 512) tile whose last sub-tile the diagonal crosses; the state
    # never leaves the registers
    "causal_T_is_bq": (512, 2, 2, 64, 512, 512, "in_place", {}),
    # 2 x 2 cells: a whole cell below the diagonal is four strips of 128
    # against all 512 keys, the state carried in scratch
    "causal_full_cells": (1024, 2, 2, 64, 512, 512, "in_place", {}),
    # the band of cells [far, whole, diagonal] as window 2048 at blocks of
    # 1024 has it
    "window_two_blocks": (2048, 1, 1, 128, 512, 512, "in_place",
                          dict(window=1024)),
    "window_half_a_block": (1024, 1, 1, 128, 512, 512, "in_place",
                            dict(window=256)),
    # off the lattice: both edges inside one sub-tile row
    "window_300": (1024, 2, 2, 64, 512, 512, "in_place", dict(window=300)),
    "grouped_kv_heads": (1024, 4, 1, 128, 512, 512, "in_place", {}),
    "grouped_kv_heads_window": (1024, 4, 2, 128, 512, 512, "in_place",
                                dict(window=640)),
    "block_diffusion": (1024, 2, 1, 128, 256, 256, "in_place",
                        dict(block=4)),
    "packed": (1024, 3, 3, 64, 512, 512, "packed", {}),
    "packed_grouped_window": (1024, 4, 2, 64, 512, 512, "packed",
                              dict(window=384)),
    "not_causal": (512, 2, 2, 64, 256, 256, "in_place", dict(causal=False)),
    # cells the diagonal crosses off the lattice: one masked tile a strip
    "masked_bq_is_not_bk": (1024, 2, 2, 64, 512, 256, "in_place", {}),
    # a ring partial: runtime offsets, every strip masked, rows that see
    # nothing
    "masked_runtime_offsets": (512, 2, 2, 64, 256, 256, "in_place",
                               dict(q_off=128, k_off=384)),
    "masked_runtime_offsets_packed": (512, 1, 1, 32, 512, 512, "packed",
                                      dict(q_off=256, k_off=256)),
    # seven query heads a KV head (SmallThinker's 28 / 4: no power of two
    # on the dk/dv kernel's fourth axis nor in ``h // group``), full and
    # under a window FOUR blocks wide: the band is [far, whole, whole,
    # whole, diagonal], as 4,096 keys at blocks of 1,024 have it
    "group7": (1024, 7, 1, 128, 256, 256, "in_place", {}),
    "group7_two_kv_heads": (512, 14, 2, 128, 256, 256, "in_place", {}),
    "group7_window_four_blocks": (2048, 7, 1, 128, 256, 256, "in_place",
                                  dict(window=1024)),
    "group7_packed": (1024, 7, 1, 64, 256, 256, "packed", {}),
    "group7_window_four_blocks_packed": (2048, 7, 1, 64, 256, 256,
                                         "packed", dict(window=1024)),
}


class TestStripWalk:
    """The forward walks every cell as strips of queries, each against the
    keys it sees of the cell as one tile (``_fwd_kernel``; PR 42)."""

    @pytest.mark.parametrize("case", sorted(_WALKS))
    def test_output_and_lse_are_the_dense_ones(self, case):
        from horovod_tpu.ops import flash_attention as F

        T, H, Hkv, D, bq, bk, layout, mask = _WALKS[case]
        mask = dict(mask)
        causal = mask.pop("causal", True)
        offsets = {k: jnp.int32(mask.pop(k)) for k in ("q_off", "k_off")
                   if k in mask}
        rs = np.random.RandomState(len(case))
        q, k, v = (jnp.asarray(rs.randn(1, T, n, D), jnp.float32)
                   for n in (H, Hkv, Hkv))
        seen = _seen(T, causal=causal, **mask,
                     **{k: int(x) for k, x in offsets.items()})
        o_d, lse_d = _dense_o_lse(q, k, v, seen)
        if layout == "packed":
            args, heads = [F._pack(x) for x in (q, k, v)], 1
        else:
            args, heads = [x.reshape(1, T, -1) for x in (q, k, v)], H
        o, lse = F._flash_fwd(
            *args, D ** -0.5, causal, bq, bk, heads=heads, group=H // Hkv,
            static_skip=not offsets, **offsets, **mask)
        o = F._unpack(o, 1, H) if layout == "packed" else o.reshape(q.shape)
        rows = seen.any(axis=1)
        np.testing.assert_allclose(np.asarray(o)[:, rows],
                                   np.asarray(o_d)[:, rows],
                                   rtol=1e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(lse)[:, 0][:, rows],
                                   np.asarray(lse_d)[:, rows],
                                   rtol=1e-5, atol=2e-6)
        assert not np.asarray(o)[:, ~rows].any()
        assert (np.asarray(lse)[:, 0][:, ~rows] == -1e30).all()

    @pytest.mark.parametrize("case", sorted(
        c for c in _WALKS if c.startswith("group7")))
    def test_group_of_seven_gradients_are_the_dense_ones(self, case,
                                                         monkeypatch):
        """The public call at seven query heads a KV head, full and under
        the four-block window, in place and packed: o, dq, and dk, dv
        summed over the seven by the dk/dv kernel's fourth grid axis."""
        from horovod_tpu.ops import flash_attention as F

        T, H, Hkv, D, bq, bk, layout, mask = _WALKS[case]
        if layout == "packed":
            monkeypatch.setattr(F, "_reads_in_place", lambda H, D: False)
        q, k, v, w = _grouped_qkv(T, H, Hkv, D, seed=len(case))
        before, groups = _layout_counts(), counter("flash.kv_group").value
        got = _fwd_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=mask.get("window"), block_q=bq,
            block_k=bk), q, k, v, w)
        want = _fwd_and_grads(lambda q, k, v: _dense_window(
            q, k, v, mask.get("window")), q, k, v, w)
        _assert_close(got, want, 2e-4)
        assert got[2].shape == k.shape and got[3].shape == v.shape
        assert _layout_counts()[layout] > before[layout]
        assert (counter("flash.kv_group").value - groups) % 7 == 0
        if "window" in mask:
            assert F._band_blocks(mask["window"], bk, T // bk) == 5

    @pytest.mark.parametrize("T,bq,bk,kw,tiles,strips", [
        # the GPT-2 cells: a head is one cell that skips, four strips
        (1024, 1024, 1024, {}, (16, 10, 4), 4),
        # trinity-mini's full call: 28 whole cells of eight strips, eight
        # on the diagonal of four
        (8192, 1024, 1024, {}, (604, 108, 32), 256),
        (8192, 1024, 1024, dict(window=2048), (919, 147, 56), 112),
        # phi-4-mini-flash's sliding call at either blocking
        (8192, 1024, 1024, dict(window=512), (1024, 93, 62), 46),
        (8192, 512, 512, dict(window=512), (1024, 93, 62), 62),
        # sdar: the block-diffusion grid over 16,384 rows
        (16384, 1024, 1024, dict(block=4), (3256, 248, 96), 544),
        (2048, 1024, 512, {}, (36, 18, 6), 40),
        (1024, 512, 512, {}, (13, 7, 4), 8),
        # a ring partial: one masked tile a strip of 128
        (1024, 1024, 1024, dict(q_off=jnp.int32(1024), k_off=jnp.int32(0),
                                static_skip=False), (1, 1, 1), 8),
    ])
    def test_counters(self, T, bq, bk, kw, tiles, strips):
        """``flash.strips`` and ``flash.softmax_updates`` of the forward:
        one update a strip, fewer than one a computed sub-tile wherever
        the call's cells are cut; ``flash.tiles_*`` read what they read
        before the forward walked by strips (the pairs computed are the
        same)."""
        from horovod_tpu.ops import flash_attention as F

        labels = dict(kernel="fwd", **{k: str(kw[k]) for k in (
            "window", "block") if k in kw})
        names = ("flash.tiles_total", "flash.tiles_computed",
                 "flash.tiles_masked", "flash.strips",
                 "flash.softmax_updates")
        read = lambda: [counter(n, **labels).value for n in names]
        before = read()
        x = jax.ShapeDtypeStruct((1, T, 128), jnp.bfloat16)
        jax.eval_shape(lambda q: F._flash_fwd(
            q, q, q, 0.125, True, bq, bk, **kw), x)
        got = [a - b for a, b in zip(read(), before)]
        assert tuple(got[:3]) == tiles
        assert got[3] == got[4] == strips
        cut = [F._cuts(mode) for mode in F._grid_cells(
            True, kw.get("static_skip", True), T // bq, T // bk, bq, bk,
            kw.get("window"), kw.get("block")).values() if mode is not None]
        if all(cut):
            assert strips < tiles[1]
        # ... and the backward kernels count no walk of their own.
        assert not counter("flash.strips", kernel="bwd_dq").value
