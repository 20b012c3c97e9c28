#!/usr/bin/env python3
"""Device time of the three flash-attention kernels, by sub-tile size.

    chiprun -- python3 scripts/flash_kernel_times.py \
        --shapes 8x1024x16x64c,16x1024x12x64c,1x8192x12x64c,8x1024x16x64f \
        --subtiles 128x128,256x256,512x512,256x512

Runs ``jax.grad(flash_attention)`` a few times under the profiler for each
(shape, sub-tile) and prints the mean duration of the events named
``hvd_flash_fwd`` / ``hvd_flash_bwd_dq`` / ``hvd_flash_bwd_dkv`` on the
first device: the kernels alone, no dispatch. The operands are what the
model's projections give, ``[B, T, H * D]`` arrays seen as ``[B, T, H, D]``
inside the jitted function, so a kernel is timed on the layout it meets in
a step; ``other`` is every other device op of a call together (the layout
traffic and ``delta``; the test's own ``sum`` and its cotangent are a few
us of it). A shape is ``BxTxHxD`` + ``c`` (causal) or ``f`` (full), then
``.kvN`` for N KV heads under the H query heads and ``.wN`` for a window of
N keys (``1x8192x32x128c.kv4.w2048``: a windowed call's events carry the
names ``hvd_flash_*_win``, so one windowed and one grouped call are timed
alone against the full call ``1x8192x32x128c``) and ``.bdN`` for the
block-diffusion mask of block length N over T = 2 L rows
(``1x16384x32x128c.kv4.bd4``: the ``hvd_flash_*_bd`` kernels); a
sub-tile ``TQxTK`` (the rule's own choice when the list is empty). ``--module FILE`` times another
copy of ``ops/flash_attention.py`` (e.g. the parent commit's) in the same
process; such a copy ignores ``--subtiles`` unless it has ``_SUB_TILE``.
Beside each kernel's time stands ``mxu_share``: the share of it that the
matmuls the kernel EXECUTES need at the MXU's peak (the sub-tiles it visits,
``flash.tiles_computed``'s, x 2 / 3 / 4 matmuls a tile forward / dq / dk-dv
x 128 lanes, a head's width on the MXU in place, D packed), so that the
forward's rate a matmul reads against the backward's in one line
(PERF.md section 5, PR 42: the default ``--shapes`` are its table;
``phi-4-mini-flash``'s 40 / 20 heads of 64 reach the kernels as 40 / 10 of
128, ``.kv10``: differential attention is one 128-wide call). Needs a TPU (anything else: exit 2). Results also go to
``chiprun_out/flash_kernel_times.jsonl``.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv",
           "hvd_flash_fwd_win", "hvd_flash_bwd_dq_win",
           "hvd_flash_bwd_dkv_win", "hvd_flash_fwd_bd",
           "hvd_flash_bwd_dq_bd", "hvd_flash_bwd_dkv_bd")


def load_module(path):
    if not path:
        from horovod_tpu.ops import flash_attention as mod

        return mod
    # Under the package's name, so that the copy's relative imports hold.
    spec = importlib.util.spec_from_file_location(
        "horovod_tpu.ops._flash_other", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def kernel_us(trace_dir, kernels=KERNELS, calls=0):
    """{kernel name: (mean us per event, events)} on the first device of a
    trace, for the ``pallas_call`` names ``kernels``; with ``calls``, also
    ``other``: (us of every other op per call, their events)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    planes = [p for p in ProfileData.from_file(path).planes
              if p.name.startswith("/device:TPU:")]
    plane = min(planes, key=lambda p: p.name)
    found = {k: [] for k in kernels}
    other = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            # "hvd_flash_bwd_dq" must not also count under "..._dkv".
            mine = [k for k in kernels
                    if f"%{k}." in e.name or f"%{k} " in e.name]
            for k in mine:
                found[k].append(e.duration_ns * 1e-3)
            if not mine:
                other.append(e.duration_ns * 1e-3)
    us = {k: (sum(v) / len(v), len(v)) for k, v in found.items() if v}
    if calls and other:
        us["other"] = (sum(other) / calls, len(other))
    return us


MATMULS = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}   # a score tile
# One call of each cell's kind (PERF.md section 5): the GPT-2 cells',
# trinity-mini's full and windowed, phi-4-mini-flash's, sdar's.
TABLE = ("8x1024x16x64c,1x8192x32x128c.kv4,1x8192x32x128c.kv4.w2048,"
         "1x8192x40x128c.kv20,1x8192x40x128c.kv20.w512,"
         "1x16384x32x128c.kv4.bd4")


def executed_matmul_us(shape, block, peak_flops):
    """{``fwd`` | ``bwd_dq`` | ``bwd_dkv``: us} the matmuls a kernel of the
    call executes need at ``peak_flops``: the pairs of the sub-tiles it
    visits (the tree's own lattice: a copy given by ``--module`` visits the
    same pairs) x 2 x the head's width on the MXU x matmuls a tile."""
    from horovod_tpu.ops import flash_attention as F

    B, T, H, D, causal, kv_heads, window, bd = shape
    b = F._pick_block(T // (2 if bd else 1), block)
    n = T // b
    pairs = 0
    for mode in F._grid_cells(causal, True, n, n, b, b, window, bd).values():
        if mode is not None:
            tq, tk = F._sub_tiles(mode, b, b, F._SUB_TILE)
            pairs += tq * tk * sum(len(F._k_plan(mode, a, tq, b, tk))
                                   for a in range(b // tq))
    width = 128 if F._in_place(H, kv_heads, D) else D
    flops = 2.0 * pairs * width * B * H
    return {k: m * flops / peak_flops * 1e6 for k, m in MATMULS.items()}


def mxu_shares(us, shape, block, peak_flops):
    """{kernel name: executed matmuls' us / measured us} of one row."""
    need = executed_matmul_us(shape, block, peak_flops)
    return {name: round(need_us / mean_us, 4)
            for name, (mean_us, _) in us.items()
            for kind, need_us in need.items()
            if name.startswith("hvd_flash_" + kind)}


def peak_flops():
    """The bf16 peak of this process's chip (``benchmarks/lib/peaks.json``:
    a chip that is not in the table is an error, not a default)."""
    import jax

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "lib", "peaks.json")) as f:
        return json.load(f)[jax.devices()[0].device_kind]["bf16_flops_per_s"]


def time_one(mod, shape, block, steps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    B, T, H, D, causal, kv_heads, window, bd = shape
    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(B, T, n * D), jnp.bfloat16) * 0.3
               for n in (H, kv_heads, kv_heads))
    # Only what the shape asks for: a copy from before the window (the
    # parent's, by --module) is still called as it was.
    extra = {} if window is None else {"window": window}
    if bd is not None:
        extra["block_diffusion"] = bd

    @jax.jit
    def f(q, k, v):
        return jax.grad(lambda q, k, v: mod.flash_attention(
            *(x.reshape(B, T, -1, D) for x in (q, k, v)), causal=causal,
            block_q=block, block_k=block, **extra,
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    jax.block_until_ready(f(q, k, v))        # compile + warm
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(steps):
            out = f(q, k, v)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        return kernel_us(d, calls=steps)


def parse_shape(text: str):
    """``BxTxHxD{c|f}[.kvN][.wN][.bdN]`` -> (B, T, H, D, causal, KV heads,
    window or None, block-diffusion block length or None)."""
    base, *options = text.split(".")
    B, T, H, D = map(int, base[:-1].split("x"))
    kv_heads, window, bd = H, None, None
    for opt in options:
        if opt.startswith("kv"):
            kv_heads = int(opt[2:])
        elif opt.startswith("bd"):
            bd = int(opt[2:])
        elif opt.startswith("w"):
            window = int(opt[1:])
        else:
            raise ValueError(f"shape option {opt!r} in {text!r}")
    return B, T, H, D, base[-1] == "c", kv_heads, window, bd


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=TABLE,
                    help="comma-separated; default: PERF.md section 5's table")
    ap.add_argument("--subtiles", default="")
    ap.add_argument("--blocks", default="1024",
                    help="grid blocks (bq = bk), comma-separated")
    ap.add_argument("--module", default="")
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print("flash_kernel_times: needs a TPU", file=sys.stderr)
        return 2
    mod = load_module(args.module)
    peak = peak_flops()
    shapes = [parse_shape(s) for s in args.shapes.split(",")]
    subs = [tuple(map(int, s.split("x")))
            for s in args.subtiles.split(",") if s] or [None]
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_kernel_times.jsonl", "a") as log:
        for shape in shapes:
            for block in map(int, args.blocks.split(",")):
                for sub in subs:
                    if sub is not None and hasattr(mod, "_SUB_TILE"):
                        mod._SUB_TILE = sub
                    try:
                        us = time_one(mod, shape, block, args.steps)
                    except Exception as e:   # a sub-tile Mosaic refuses
                        us = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
                    row = {"module": args.module or "tree", "shape": shape,
                           "block": block, "subtile": sub, "us": us}
                    if "error" not in us:
                        row["mxu_share"] = mxu_shares(us, shape, block, peak)
                    print(json.dumps(row), flush=True)
                    log.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
