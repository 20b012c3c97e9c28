#!/usr/bin/env python3
"""What the pieces of ``moe_router`` and of ``moe_ffn_dropless``'s plan cost
alone on the chip (ROADMAP S13(h); PERF.md, PR 38), and what adding ``N``
rows into a ``[V, C]`` table costs by the form it is written in (``--rows
embed``: the lookup's backward, PERF.md, PR 46).

    chiprun -- python3 scripts/router_piece_times.py [--rows router|embed]

``router``: one JSON line a token count (16,384 and 8,192; 128 experts, 8 a
token): microseconds on the host's clock around ONE jitted op, the first
quartile of 30 calls; ``noop`` (an elementwise pass over the scores) is what
a call costs anyway, so read every other row less that one. ``onehot_read``
and ``onehot_scatter`` give the same numbers as ``take_along_axis`` and its
transpose (one nonzero term a sum).

``embed``: one JSON line a (``N``, ``V``, ``C``, dtype): DEVICE microseconds
a call by the profiler's trace (every device op of the jitted function,
mean of 5 calls, the four longest ops by name beside it) of
``zeros([V, C]).at[ids].add(rows)`` as written and in the forms
``EMBED_FORMS`` names, ids uniform over ``V`` as
``benchmarks/lib/traffic.py`` draws them; ``gather`` (``table[ids]``) is the
compiler's rate for the same rows and ``embed_lookup_bwd`` what
``horovod_tpu.ops.embed_lookup`` runs (``gap``: its largest distance from
the float32 scatter-add of the same rows; ``--blocks`` sweeps its grid).
Rows also go to ``chiprun_out/embed_scatter_times.jsonl``.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

E, K = 128, 8


def timed(f, *args, n=30):
    f = jax.jit(f)
    jax.block_until_ready(f(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    return round(1e6 * sorted(ts)[n // 4], 1)


def router_rows() -> None:
    for N in (16384, 8192):
        probs = jax.nn.softmax(
            jax.random.normal(jax.random.key(0), (N, E)), -1)
        idx = lax.top_k(probs, K)[1]
        w = jax.random.normal(jax.random.key(1), (N, K))
        key = jax.random.randint(jax.random.key(2), (N * K,), 0, 17)

        def read(p, i):
            return jnp.take_along_axis(p, i, -1)

        rows = {
            "noop": timed(lambda p: p + 1.0, probs),
            "top_k": timed(lambda p: lax.top_k(p, K), probs),
            "take_along_axis": timed(read, probs, idx),
            "onehot_read": timed(lambda p, i: jnp.sum(jax.nn.one_hot(
                i, E, dtype=p.dtype) * p[:, None, :], -1), probs, idx),
            "take_along_axis_transpose": timed(jax.grad(
                lambda p, i, w: (read(p, i) * w).sum()), probs, idx, w),
            "onehot_scatter": timed(lambda i, w: jnp.sum(jax.nn.one_hot(
                i, E, dtype=w.dtype) * w[..., None], -2), idx, w),
            "sort_choices": timed(lambda k: lax.sort(
                (k, jnp.arange(N * K, dtype=jnp.int32)), num_keys=1), key),
        }
        print(json.dumps({"tokens": N, "device": jax.devices()[0].device_kind,
                          "us": rows}), flush=True)


# The shapes the five cells that call ``embed_lookup`` and the GPT-2 cells
# run, the widths between and beyond them at the slow cell's (N, V), and the
# expert walk's scatter (``moe/layer.py``: 512 rows into 16,384 tokens).
EMBED_SHAPES = (
    (8192, 25008, 2560), (16384, 37984, 2560), (8192, 25024, 2048),
    (16384, 18992, 2048), (16384, 50257, 768),
    (8192, 25008, 2048), (8192, 25008, 2304), (8192, 25008, 3072),
    (8192, 25008, 4096),
    (512, 16384, 2048), (512, 16384, 2560),
)


def _scatter(ids, rows, V):
    return jnp.zeros((V, rows.shape[1]), rows.dtype).at[ids].add(rows)


def _split_columns(ids, rows, V, at=2048):
    """Two scatters, of the first ``at`` columns and of the rest."""
    return jnp.concatenate([_scatter(ids, rows[:, :at], V),
                            _scatter(ids, rows[:, at:], V)], axis=1)


def _lanes128(ids, rows, V):
    """The table seen as ``[V * C / 128, 128]``: row ``id`` is the ``C /
    128`` rows from ``id * C / 128``."""
    N, C = rows.shape
    t = C // 128
    wide = (ids[:, None] * t + jnp.arange(t, dtype=ids.dtype)).reshape(-1)
    return _scatter(wide, rows.reshape(N * t, 128), V * t).reshape(V, C)


def _split_rows(ids, rows, V):
    """The table's rows split at ``V / 2``: each half takes the ids it
    holds and drops the others (an id past a half's end is out of range)."""
    h = V // 2
    low = jnp.where(ids < h, ids, V)
    return jnp.concatenate([
        jnp.zeros((h, rows.shape[1]), rows.dtype).at[low].add(
            rows, mode="drop"),
        jnp.zeros((V - h, rows.shape[1]), rows.dtype).at[
            jnp.where(ids >= h, ids - h, V)].add(rows, mode="drop")])


def _distinct_hinted(ids, rows, V):
    """What a scatter of DISTINCT sorted ids costs when told so (the ids
    here are a sorted draw without repeats: another operation, a rate)."""
    return jnp.zeros((V, rows.shape[1]), rows.dtype).at[ids].add(
        rows, unique_indices=True, indices_are_sorted=True)


def _to_float32(ids, rows, V):
    """The parent's tied table: a bfloat16 scatter, then its conversion."""
    return _scatter(ids, rows, V).astype(jnp.float32)


def _lookup_bwd(ids, rows, V, blocks=(None, None)):
    from horovod_tpu.ops import embed_lookup as EL

    return EL.embed_grad(ids, rows, V, block_rows=blocks[0],
                         chunk_rows=blocks[1])


EMBED_FORMS = {
    "scatter": _scatter, "split_2048_rest": _split_columns,
    "lanes128": _lanes128, "split_rows": _split_rows,
    "distinct_hinted": _distinct_hinted, "scatter_then_f32": _to_float32,
    "embed_lookup_bwd": _lookup_bwd,
}


def device_us(f, *args, calls=5):
    """(device microseconds a call of ``jit(f)`` — every op of the first
    device's ``XLA Ops`` line, and the four longest by name —, its result)."""
    import glob

    from jax.profiler import ProfileData

    f = jax.jit(f)
    jax.block_until_ready(f(*args))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(calls):
            out = f(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        plane = min((p for p in ProfileData.from_file(path).planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
        by_op = {}
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    name = e.name.split(" = ")[0]
                    by_op[name] = by_op.get(name, 0.0) + e.duration_ns * 1e-3
    top = sorted(by_op, key=by_op.get, reverse=True)[:4]
    return {"us": round(sum(by_op.values()) / calls, 1),
            "top": {op: round(by_op[op] / calls, 1) for op in top}}, out


def embed_rows(forms, shapes, blocks, dtypes) -> None:
    import functools

    import numpy as np

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/embed_scatter_times.jsonl", "a") as log:
        for (N, V, C), dtype in ((s, d) for s in shapes for d in dtypes):
            rng = np.random.default_rng(N + V + C)
            ids = jnp.asarray(rng.integers(0, V, N), jnp.int32)
            rows = jnp.asarray(rng.standard_normal((N, C)), dtype)
            table = jnp.asarray(rng.standard_normal((V, C)), dtype)
            us = {"gather": device_us(lambda t, i: t[i], table, ids)[0]}
            # what the kernel is held to: the float32 scatter-add
            want = _scatter(ids, rows.astype(jnp.float32), V)
            for name in forms:
                if name == "split_2048_rest" and C <= 2048:
                    continue
                variants = {name: EMBED_FORMS[name]}
                if name == "embed_lookup_bwd":
                    variants = {
                        name + "@" + "x".join(map(str, b)) if b[0] else name:
                        functools.partial(_lookup_bwd, blocks=b)
                        for b in blocks}
                for key, form in variants.items():
                    try:
                        i = ids
                        if name == "distinct_hinted":
                            i = jnp.asarray(np.sort(rng.choice(
                                V, N, replace=False)), jnp.int32)
                        us[key], got = device_us(
                            lambda i, r: form(i, r, V), i, rows)
                        if name == "embed_lookup_bwd":
                            us[key]["gap"] = float(
                                jnp.abs(got - want).max()
                                / jnp.abs(want).max())
                    except Exception as e:  # a form the compiler refuses
                        us[key] = {
                            "error": f"{type(e).__name__}: {str(e)[:200]}"}
            row = {"N": N, "V": V, "C": C, "dtype": dtype,
                   "distinct": int(np.unique(np.asarray(ids)).size),
                   "device": jax.devices()[0].device_kind, "us": us}
            print(json.dumps(row), flush=True)
            log.write(json.dumps(row) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", choices=("router", "embed"), default="router")
    ap.add_argument("--forms", default=",".join(EMBED_FORMS),
                    help="embed: comma-separated names of EMBED_FORMS")
    ap.add_argument("--blocks", default="",
                    help="embed: the kernel's BLOCK_ROWSxCHUNK_ROWS,... "
                    "(default: the module's)")
    ap.add_argument("--dtypes", default="bfloat16,float32")
    ap.add_argument("--shapes", default="",
                    help="embed: NxVxC,... (default: EMBED_SHAPES)")
    args = ap.parse_args(argv)
    if args.rows == "router":
        router_rows()
        return 0
    if jax.default_backend() != "tpu":
        print("router_piece_times --rows embed: needs a TPU", file=sys.stderr)
        return 2
    shapes = [tuple(map(int, s.split("x")))
              for s in args.shapes.split(",") if s] or EMBED_SHAPES
    blocks = [tuple(map(int, b.split("x")))
              for b in args.blocks.split(",") if b] or [(None, None)]
    embed_rows([f for f in args.forms.split(",") if f], shapes, blocks,
               args.dtypes.split(","))
    return 0


if __name__ == "__main__":
    sys.exit(main())
