"""The main-path kernels, compiled by the TPU compiler for a described v5e.

Interpreter-mode tests (the rest of the suite) verify kernel *numerics*
but run none of the TPU compiler: a block shape Mosaic rejects, or a
kernel that wants more scoped VMEM than a v5e grants (16 MiB), passes
every one of them and fails the first time a chip sees it. libtpu is
installed here and compiles for a chip that is described, not attached
(/opt/skills/guides/on-chip-measurement, section 2), so these tests ask it
— at the widths chip_smoke.py and bench.py run, about two seconds a case,
no chip time. Nothing executes: a compile that passes is not a chip run.

All of them live in THIS file, and the topology is described inside a
module-scoped fixture, never at import: only one process at a time may
load libtpu, pytest-xdist imports every test file in every worker, and the
worker that is handed this file is the one that loads it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import horovod_tpu as hvd
from horovod_tpu.ops.selective_scan import CANDIDATES as SCAN_CANDIDATES


class _DescribedTpu:
    """A described ``v5e:2x2``: shapes placed on its chips, and compiles."""

    def __init__(self, topo):
        self.one_chip = SingleDeviceSharding(topo.devices[0])
        self.mesh = Mesh(np.array(topo.devices).reshape(2, 2), hvd.HVD_AXES)

    def on_chip(self, tree):
        """``tree``'s shapes (arrays or ShapeDtypeStructs) on chip 0."""
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=self.one_chip), tree)

    def shape(self, shape, dtype, spec=None):
        sharding = (self.one_chip if spec is None
                    else NamedSharding(self.mesh, spec))
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    @staticmethod
    def compile(fn, *args):
        """Raises what the chip's compiler would raise."""
        return jax.jit(fn).lower(*args).compile()


@pytest.fixture(scope="module")
def tpu():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but can never be read back without the chip: keep it out.
    with hvd.compile.persistent_cache_disabled():
        yield _DescribedTpu(topo)


@pytest.fixture()
def real_kernels(monkeypatch):
    """``interpret=False``: the kernels' own backend test sees this
    process's CPU and would hand Mosaic nothing to compile."""
    import horovod_tpu.ops.flash_attention as F
    import horovod_tpu.ops.layer_norm as L
    import horovod_tpu.ops.softmax_xent as X

    for mod in (F, L, X):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _custom_calls(text: str, name: str) -> int:
    """How many custom calls of the compiled ``text`` are the kernel
    ``name`` (and not one whose name goes on, as ``_dq`` after ``_bwd``)."""
    import re

    return len(re.findall(rf"%{name}[.\d]* = [^\n]*custom-call\(", text))


@pytest.mark.parametrize("B,T,H,D,causal", [
    (8, 1024, 16, 64, True),    # GPT-350M train step (chip_smoke.py)
    (16, 1024, 12, 64, True),   # GPT-124M bench shape
    (1, 8192, 12, 64, True),    # examples/gpt_long_context.py: sub-tile
                                # loops with bounds from the program ids
    (8, 1024, 16, 64, False),   # every sub-tile, no mask
    (2, 640, 4, 64, True),      # a whole-sequence block of five sub-tiles
    (2, 2048, 8, 128, True),    # a head a lane block, read in place
    (8, 1024, 3, 64, True),     # an odd head count: the packed layout
])
def test_flash_attention_compiles(tpu, real_kernels, B, T, H, D, causal):
    from horovod_tpu.ops.flash_attention import flash_attention

    q = tpu.shape((B, T, H, D), jnp.bfloat16)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=causal).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    assert _has_kernel(tpu.compile(f, q, q, q))


@pytest.mark.parametrize("T,H,Hkv,D,window,block", [
    (8192, 32, 4, 128, 2048, 1024),   # trinity-mini.train-8k-1chip, a
                                      # sliding layer: a band of 3 blocks
    (8192, 32, 4, 128, 2048, 512),    # the sweep's other candidate: 5
    (8192, 32, 4, 128, None, 1024),   # the same cell's full layer
    (8192, 32, 4, 128, None, 512),
    (4096, 8, 8, 64, 1000, 1024),     # a pair of heads a block, a window
                                      # off the sub-tile lattice
    (4096, 8, 2, 64, 2048, 1024),     # grouped at D = 64: packed
    (2048, 6, 2, 128, 300, 1024),     # a window inside one sub-tile row
    # smallthinker-21b-a3b.train-16k-1chip: SEVEN query heads a KV head
    # in place over [1, 16384, 3584], a sliding layer (a band of 5 blocks
    # of 1,024; 9 of 512) and the NoPE full layer
    (16384, 28, 4, 128, 4096, 1024),
    (16384, 28, 4, 128, 4096, 512),
    (16384, 28, 4, 128, None, 1024),
    (16384, 28, 4, 128, None, 512),
    # nemotron-3-super-120b-a12b.train-8k-1chip: the attention layer's
    # share, FOUR query heads on ONE KV head, causal, no window
    (8192, 4, 1, 128, None, 1024),
    (8192, 4, 1, 128, None, 512),
])
def test_flash_window_and_grouped_heads_compile(tpu, real_kernels, T, H,
                                                Hkv, D, window, block):
    """The windowed and grouped kernels at the cell's widths: the kernels
    carry their own names, K and V stay at their KV heads (nothing of
    q's size is made from them), and the gradients come back in k's and
    v's shapes."""
    from horovod_tpu.ops.flash_attention import flash_attention

    q = tpu.shape((1, T, H, D), jnp.bfloat16)
    kv = tpu.shape((1, T, Hkv, D), jnp.bfloat16)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, block_q=block,
            block_k=block).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    compiled = tpu.compile(f, q, kv, kv)
    text = compiled.as_text()
    tail = "_win" if window else ""
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert f"%{name}{tail}." in text or f"%{name}{tail} " in text, name
        if window:   # and no full call beside it
            assert f"%{name}." not in text and f"%{name} " not in text
    dq, dk, dv = compiled.out_info
    assert (dq.shape, dk.shape, dv.shape) == (q.shape, kv.shape, kv.shape)
    if Hkv < H and D == 128:
        # In place: K and V go into the kernels as they are. Nothing of
        # q's size is made from a value of k's size (a K repeated a query
        # head would be) but by the kernels themselves.
        import re

        kv_sized = set(re.findall(
            rf"(%[\w.\-]+) = bf16\[1,{T},{Hkv * D}\]", text))
        assert kv_sized
        for line in text.splitlines():
            if (f" = bf16[1,{T},{H * D}]" in line
                    and "custom-call(" not in line):
                used = set(re.findall(r"%[\w.\-]+", line.split(" = ", 1)[1]))
                assert not used & kv_sized, line[:200]


@pytest.mark.parametrize("L,H,Hkv,D,B,block", [
    (8192, 32, 4, 128, 4, 1024),   # sdar-30b-a3b.train-8k-1chip: 16,384
                                   # rows, 8 blocks a half
    (8192, 32, 4, 128, 4, 512),    # the sweep's other candidate
    (2048, 8, 8, 64, 8, 1024),     # a pair of heads a block
    (1024, 6, 2, 64, 4, 1024),     # grouped at D = 64: packed, one block
])
def test_flash_block_diffusion_compiles(tpu, real_kernels, L, H, Hkv, D, B,
                                        block):
    """The kernels under the block-diffusion mask at the cell's widths:
    their own names and no plain or windowed call beside them, no score
    array over the rows, gradients in k's and v's shapes."""
    import re

    from horovod_tpu.ops.flash_attention import flash_attention

    q = tpu.shape((1, 2 * L, H, D), jnp.bfloat16)
    kv = tpu.shape((1, 2 * L, Hkv, D), jnp.bfloat16)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, block_diffusion=B, block_q=block,
            block_k=block).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    compiled = tpu.compile(f, q, kv, kv)
    text = compiled.as_text()
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert _custom_calls(text, name + "_bd") >= 1, name
        assert _custom_calls(text, name) == 0, name
        assert _custom_calls(text, name + "_win") == 0, name
    assert not re.search(rf"[\[,]({L}|{2 * L}),{2 * L}\]", text)
    dq, dk, dv = compiled.out_info
    assert (dq.shape, dk.shape, dv.shape) == (q.shape, kv.shape, kv.shape)


def _sparse_shapes(tpu, T):
    """(q, k, v, index_q, index_k, index_w) at the benchmark's widths:
    32/4 heads of 128, indexer 16 x 64."""
    kv = tpu.shape((1, T, 4, 128), jnp.bfloat16)
    return (tpu.shape((1, T, 32, 128), jnp.bfloat16), kv, kv,
            tpu.shape((1, T, 16, 64), jnp.bfloat16),
            tpu.shape((1, T, 64), jnp.bfloat16),
            tpu.shape((1, T, 16), jnp.float32))


@pytest.mark.parametrize("T,backward", [
    # keye-vl2-30b-a3b.train-16k-1chip: [128, T] index keys (8 MB), (1024,
    # 1024) score tiles x 8 query heads forward, (512, 1024) backward
    # beside a KV head's 16 MB of float32 dk and dv, all in VMEM
    (16384, ("hvd_sparse_attn_bwd",)),
    # one attention block a row, two index chunks
    (2048, ("hvd_sparse_attn_bwd",)),
    # dk and dv of a KV head (32 MB) over the fused kernel's budget
    (32768, ("hvd_sparse_attn_bwd_dq", "hvd_sparse_attn_bwd_dkv")),
])
def test_sparse_attention_compiles(tpu, real_kernels, T, backward):
    """The index kernel and the masked-attention kernels at the
    benchmark's widths: 32/4 heads of 128, indexer 16 x 64, topk 2048; the
    backward is the one kernel or the two, by the sequence's length."""
    from horovod_tpu.ops.sparse_attention import sparse_attention

    def f(q, k, v, qi, ki, w):
        return jax.grad(lambda q, k, v: sparse_attention(
            q, k, v, qi, ki, w, topk=2048).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = tpu.compile(f, *_sparse_shapes(tpu, T)).as_text()
    for name in ("hvd_index_select", "hvd_sparse_attn_fwd", *backward):
        assert _custom_calls(text, name) == 1, name
    for name in {"hvd_sparse_attn_bwd", "hvd_sparse_attn_bwd_dq",
                 "hvd_sparse_attn_bwd_dkv"} - set(backward):
        assert _custom_calls(text, name) == 0, name


@pytest.mark.parametrize("T,Dn,N,chunk,block_d", [
    # phi-4-mini-flash.train-8k-1chip at every blocking its sweep times: the
    # backward takes all 40 rows of channels a grid step at chunk 64 (37 MB
    # of VMEM by its own plan), one register of them at chunk 128
    *((8192, 5120, 16, chunk, block_d)
      for chunk, block_d in SCAN_CANDIDATES),
    # two groups of 16 states (dx and ddt added to a second time a token),
    # channels that fill no block
    (2048, 2100, 32, 64, 1024),
    (2048, 1300, 8, 128, 2048),
])
def test_selective_scan_compiles(tpu, real_kernels, T, Dn, N, chunk,
                                 block_d):
    """The scan's two kernels, forward once and ONE backward kernel."""
    from horovod_tpu.ops.selective_scan import selective_scan

    tokens = tpu.shape((1, T, Dn), jnp.bfloat16)
    states = tpu.shape((1, T, N), jnp.bfloat16)

    def f(*ops):
        return jax.grad(lambda *o: selective_scan(
            *o, chunk=chunk, block_d=block_d).sum(),
            argnums=tuple(range(6)))(*ops)

    text = tpu.compile(f, tokens, tpu.shape((1, T, Dn), jnp.float32),
                       tpu.shape((Dn, N), jnp.float32), states, states,
                       tpu.shape((Dn,), jnp.float32)).as_text()
    assert _custom_calls(text, "hvd_selective_scan_fwd") == 1
    assert _custom_calls(text, "hvd_selective_scan_bwd") == 1


@pytest.mark.parametrize("T,h,G,chunk", [
    (8192, 16, 1, 128),   # nemotron-3-super-120b-a12b.train-8k-1chip
    (8192, 128, 8, 128),  # the published counts: eight groups of 16 heads
    (1000, 16, 2, 128),   # a last chunk the tokens do not fill
])
def test_ssd_scan_compiles_with_no_state_a_token(tpu, T, h, G, chunk):
    """Mamba-2's chunked scan, forward and backward, at the cell's widths
    (64 channels a head, 128 states): matmuls the TPU compiler takes as
    they are, the chunk states the largest thing kept, and no ``[T, h, 64,
    128]`` array in either direction."""
    from horovod_tpu.ops.ssd_scan import ssd_scan

    xs = tpu.shape((1, T, h, 64), jnp.bfloat16)
    bc = tpu.shape((1, T, G, 128), jnp.bfloat16)
    heads = tpu.shape((h,), jnp.float32)

    def f(*ops):
        return jax.grad(lambda *o: ssd_scan(*o, chunk=chunk).astype(
            jnp.float32).sum(), argnums=tuple(range(6)))(*ops)

    compiled = tpu.compile(f, xs, tpu.shape((1, T, h), jnp.float32), heads,
                           bc, bc, heads)
    text = compiled.as_text()
    assert f"[1,{T},{h},64,128]" not in text
    assert f"{T},{h},64,128]" not in text
    chunks = -(-T // chunk)
    states = chunks * h * 64 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 24 * states


@pytest.mark.parametrize("T,H,D,dtype", [
    (8192, 40, 64, jnp.bfloat16),   # phi-4-mini-flash.train-8k-1chip
    (8192, 40, 64, jnp.float32),    # its blocks at twice the bytes
    (1000, 6, 128, jnp.bfloat16),   # a last block the rows do not fill,
                                    # 256-lane heads, three pairs
])
def test_diff_attention_kernels_compile(tpu, real_kernels, T, H, D, dtype):
    """Differential attention's two elementwise halves, a kernel each
    direction, over the arrays as they lie."""
    from horovod_tpu.ops.diff_attention import diff_combine, lay_in_halves

    def f(q, lam, scale):
        def loss(q, lam, scale):
            o = lay_in_halves(q, D)        # stands in for the flash call
            return (diff_combine(o, lam, scale, 1e-5).astype(
                jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, lam, scale)

    compiled = tpu.compile(f, tpu.shape((1, T, H * D), dtype),
                           tpu.shape((), jnp.float32),
                           tpu.shape((2 * D,), jnp.float32))
    text = compiled.as_text()
    import re

    for name in ("hvd_diff_lay_fwd", "hvd_diff_lay_bwd",
                 "hvd_diff_combine_fwd", "hvd_diff_combine_bwd"):
        # outside a named scope the call is ``%jvp_<name>_.1``
        assert len(re.findall(rf"%\w*{name}[\w.]* = [^\n]*custom-call\(",
                              text)) == 1, name
    # no float32 copy of the wide array, no view with the pair as a
    # dimension beside the lanes
    assert f"f32[1,{T},{H * 2 * D}]" not in text or dtype == jnp.float32
    assert f",2,{2 * D}]" not in text


@pytest.mark.parametrize("limit_mb", [
    96,   # the kernels' limit
    72,   # a quarter of it stays free beside the compiler's own use in a
          # whole step (the call was taken at 64 MB and refused at 56)
])
def test_fused_sparse_backward_fits_its_vmem(tpu, real_kernels, monkeypatch,
                                             limit_mb):
    """``hvd_sparse_attn_bwd`` at the benchmark's ``[4 KV heads, 8 query
    heads each, 16384, 128]`` with its own blocks: one custom call gives
    dq, dk and dv inside the VMEM limit."""
    from horovod_tpu.ops import sparse_attention as sa

    monkeypatch.setattr(sa, "_VMEM_LIMIT", limit_mb * 1024 * 1024)
    T = 16384
    q = tpu.shape((4, 8, T, 128), jnp.bfloat16)
    kv = tpu.shape((4, T, 128), jnp.bfloat16)
    row = tpu.shape((4, 8, T, 8), jnp.float32)
    assert sa._fused_bwd_fits(T, 128)

    def f(q, k, v, mask, do, lse, delta):
        return sa._bwd_call(q, k, v, mask, do, lse, delta,
                            **sa._kw(q, 128 ** -0.5, 4, fused_bwd=True))

    text = tpu.compile(f, q, kv, kv, tpu.shape((1, T, T), jnp.int8), q,
                       row, row).as_text()
    assert _custom_calls(text, "hvd_sparse_attn_bwd") == 1
    assert "hvd_sparse_attn_bwd_d" not in text


@pytest.mark.parametrize("kept,index_calls", [
    (("OUT_NAME", "SELECTION_NAME"), 1),   # models/sparse_moe_decoder.py's
    (("OUT_NAME",), 2),                    # the policy of before
])
def test_saved_selection_leaves_one_index_kernel(tpu, real_kernels, kept,
                                                 index_calls):
    """A rematerialised block that keeps the packed selection selects
    once, in the program the TPU compiler makes of it, and what it keeps
    is packed and unpacked in passes that put no second ``[T, T]`` array
    into HBM (the whole mask compared before it is cut, or the bytes
    broadcast before they are shifted)."""
    from horovod_tpu.ops import sparse_attention as sa

    T = 2048
    policy = jax.checkpoint_policies.save_only_these_names(
        *(getattr(sa, name) for name in kept))

    def f(q, k, v, qi, ki, w):
        # the scalings stand for the block's projections: work between
        # the block's input and the op that is recomputed too
        block = jax.checkpoint(
            lambda q, k, v: sa.sparse_attention(
                q * 2, k, v, qi * 2, ki, w, topk=2048), policy=policy)
        return jax.grad(lambda q, k, v: block(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = tpu.compile(f, *_sparse_shapes(tpu, T)).as_text()

    assert _custom_calls(text, "hvd_index_select") == index_calls
    for name in ("hvd_sparse_attn_fwd", "hvd_sparse_attn_bwd"):
        assert _custom_calls(text, name) == 1
    for name in ("hvd_sparse_attn_bwd_dq", "hvd_sparse_attn_bwd_dkv"):
        assert _custom_calls(text, name) == 0
    if "SELECTION_NAME" in kept:
        assert f"u8[1,{T // 8},{T}]" in text
        for whole in (f"u8[1,{T},{T}]", f"u8[8,{T // 8},{T}]"):
            assert whole not in text


@pytest.mark.parametrize("B,T,C", [
    (8, 1024, 1024),   # 350M blocks: the shape Mosaic once refused
    (16, 1024, 768),   # 124M bench shape
    (1, 7, 256),       # N < 8 rows: single whole-array block
    (2, 300, 512),     # N not a block multiple: padded rows
])
def test_ln_residual_compiles(tpu, real_kernels, B, T, C):
    from horovod_tpu.ops.layer_norm import ln_residual

    x = tpu.shape((B, T, C), jnp.bfloat16)
    g = tpu.shape((C,), jnp.float32)

    def f(x, r, g, b):
        def loss(x, r, g, b):
            y, h = ln_residual(x, r, g, b, 1e-6)
            return y.astype(jnp.float32).sum() + h.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, r, g, b)

    assert _has_kernel(tpu.compile(f, x, x, g, g))


@pytest.mark.parametrize("B,T,V,C,dtype,sharded", [
    (1, 8192, 25008, 2560, jnp.bfloat16, False),   # phi-4-mini-flash
    (1, 16384, 37984, 2560, jnp.bfloat16, False),  # smallthinker-21b-a3b
    (1, 16384, 18992, 2048, jnp.bfloat16, False),  # the sparse cell, sdar
    (1, 8192, 25008, 2560, jnp.float32, False),    # the MXU at ``highest``
    (4, 8192, 25008, 2560, jnp.bfloat16, True),    # a sequence a chip
    (1, 1000, 50257, 768, jnp.bfloat16, False),    # N fills no whole chunk
])
def test_embed_lookup_backward_compiles(tpu, real_kernels, B, T, V, C, dtype,
                                        sharded):
    """The lookup's backward is ONE kernel over the sorted rows and the
    compiler's scatter is gone; inside ``shard_map`` the kernel's operands
    agree on what varies and the table's cotangent is the chips' sum."""
    from horovod_tpu.ops.embed_lookup import embed_lookup

    def grad(table, tokens):
        def loss(table):
            with jax.named_scope("hvd.embed"):
                x = embed_lookup(table, tokens, dtype)
            return (x.astype(jnp.float32) ** 2).sum()
        return jax.grad(loss)(table)

    if sharded:
        f = hvd.shard_map(grad, mesh=tpu.mesh,
                          in_specs=(P(), P(hvd.HVD_AXES)), out_specs=P())
        args = (tpu.shape((V, C), jnp.float32, P()),
                tpu.shape((B, T), jnp.int32, P(hvd.HVD_AXES)))
    else:
        f = grad
        args = (tpu.shape((V, C), jnp.float32),
                tpu.shape((B, T), jnp.int32))
    text = tpu.compile(f, *args).as_text()
    import re

    assert len(re.findall(
        r"%\w*hvd_embed_rows_add[\w.]* = [^\n]*custom-call\(", text)) == 1
    assert not re.findall(r" scatter\(", text)
    assert ("all-reduce" in text) == sharded
    # the table is neither rounded whole nor made in bfloat16
    assert f"bf16[{V},{C}]" not in text


@pytest.mark.parametrize("entry,N,V,C", [
    ("auto", 32768, 131072, 768),    # the envelope: dense logits = 17 GB
    ("fused", 8192, 32000, 1024),    # GPT-350M head, bench --lm-loss fused
    ("fused", 8192, 50304, 2048),    # ROADMAP R1 width
    ("public", 8192, 32000, 768),
    ("public", 8192, 131072, 1024),  # widest vocab block the snap allows
    ("public", 8192, 131072, 2048),
])
def test_lm_head_compiles_at_every_width(tpu, real_kernels, entry, N, V, C):
    """The backward's scoped VMEM grows with block·C: the default blocks
    are derived from C (ops/softmax_xent.py:_default_blocks), and every
    entry point compiles at C in {768, 1024, 2048} with no knob set."""
    x = tpu.shape((N, C), jnp.bfloat16)
    w = tpu.shape((V, C), jnp.bfloat16)
    y = tpu.shape((N,), jnp.int32)

    def head(x, w, y):
        if entry == "public":
            return hvd.linear_cross_entropy(x, w, y)
        return hvd.lm_head_loss(x, w, y, mode=entry)

    def f(x, w, y):
        return jax.grad(lambda x, w: head(x, w, y).mean(),
                        argnums=(0, 1))(x, w)

    assert _has_kernel(tpu.compile(f, x, w, y))


@pytest.mark.parametrize("N,V,C", [(8192, 32000, 1024),
                                   (8192, 50304, 2048)])
def test_every_block_the_sweep_may_pick_compiles(tpu, real_kernels,
                                                 N, V, C):
    """On a chip the blocks come from the sweep, not from the default
    (the sweep is off here: the backend is the CPU). Its candidates are
    the default and halves of it — none above the rule — and each one
    compiles, so whatever wins the timing is a block the chip accepts."""
    from horovod_tpu.ops import kernel_autotune
    from horovod_tpu.ops.softmax_xent import _default_blocks, _pick_block

    default = _default_blocks(C)
    cands = kernel_autotune.xent_candidates(N, V, default, _pick_block)
    assert default in cands and len(cands) == 4
    assert all(bn <= default[0] and bv <= default[1] for bn, bv in cands)
    x = tpu.shape((N, C), jnp.bfloat16)
    w = tpu.shape((V, C), jnp.bfloat16)
    y = tpu.shape((N,), jnp.int32)
    for bn, bv in cands:
        if (bn, bv) == default:
            continue   # test_lm_head_compiles_at_every_width
        assert _has_kernel(tpu.compile(
            lambda x, w, y: jax.grad(
                lambda x, w: hvd.linear_cross_entropy(
                    x, w, y, block_n=bn, block_v=bv).mean(),
                argnums=(0, 1))(x, w), x, w, y)), (bn, bv)


def test_fixed_1024_row_block_is_what_the_rule_avoids(tpu, real_kernels):
    """The old constant default, pinned by hand: refused, scoped VMEM."""
    x = tpu.shape((8192, 1024), jnp.bfloat16)
    w = tpu.shape((32000, 1024), jnp.bfloat16)
    y = tpu.shape((8192,), jnp.int32)

    def f(x, w, y):
        return jax.grad(lambda x: hvd.linear_cross_entropy(
            x, w, y, block_n=1024, block_v=1024).mean())(x)

    with pytest.raises(Exception, match="(?i)vmem"):
        tpu.compile(f, x, w, y)


def test_fused_ln_gpt_block_compiles(tpu, real_kernels):
    """The composition a chip runs: full fwd+bwd of fused-LN GPT blocks at
    d_model 1024 (flash attention + ln_residual in one program)."""
    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=1024, num_layers=2, num_heads=16,
                    d_model=1024, d_ff=4096, max_seq_len=1024,
                    attention="flash", fused_ln=True)
    model = GPT(cfg)
    tokens = tpu.shape((8, 1024), jnp.int32)
    params = tpu.on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 1024), jnp.int32)))["params"])

    def f(p, tokens):
        return jax.grad(lambda p: model.apply(
            {"params": p}, tokens).astype(jnp.float32).mean())(p)

    assert _has_kernel(tpu.compile(f, params, tokens))


def test_quantized_allreduce_compiles_over_four_chips(tpu):
    """The int8 all_to_all (hop 2), the masked int8 psum (hop 3) and the
    round/clip/convert quantize math, partitioned over the 2x2 mesh."""

    def f(x, r):
        def spmd(v, res):
            out, nr = hvd.quantized_allreduce(v[0], res[0], op=hvd.Sum)
            return out, nr[None]

        return hvd.shard_map(spmd, mesh=tpu.mesh,
                             in_specs=(P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
                             out_specs=(P(), P(hvd.HVD_AXES)))(x, r)

    x = tpu.shape((4, 1024), jnp.float32, P(hvd.HVD_AXES))
    text = tpu.compile(f, x, x).as_text()
    assert "all-to-all" in text and "s8[" in text
