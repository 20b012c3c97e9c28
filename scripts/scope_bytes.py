#!/usr/bin/env python3
"""Bytes a cell's compiled step moves under each ``hvd.*`` scope, outside
its matmuls and kernels, read off the step compiled for a DESCRIBED v5e
with no chip attached.

    JAX_PLATFORMS=cpu python3 scripts/scope_bytes.py --workload <cell> [--scope hvd.diff_attention]

Compiles the step as ``benchmarks/rehearse_compile.py`` does (1 to 3
minutes of sandbox CPU) and walks the ENTRY computation of
``compiled.as_text()``: an instruction moves the bytes of its operands and
of its result (a fusion is one instruction: what it reads and writes in
HBM, not what it holds in registers); bitcasts, tuples,
``get-tuple-element``s, constants and parameters move nothing. It goes to
the innermost ``hvd.*`` name of its own ``op_name`` and to that path's
direction (``forward`` / ``remat`` / ``backward``:
``horovod_tpu/monitor/hlo_owners.py`` ``key_of``). A fusion that holds a
``dot`` or a ``convolution``, and a custom call (a Pallas kernel), stand in
columns of their own: their time is no question of bytes. Prints a line a
scope and direction with instructions, GB and the ms those bytes take at
``benchmarks/lib/peaks.json``'s HBM rate; with ``--scope`` that scope's
instructions by bytes, with their result shapes. ``--text <file>`` reads a
compiled text kept earlier in place of compiling, and writes it where the
file is not there yet.

Nothing runs, so the ms are a FLOOR computed from bytes, never a device
time: hold them beside the traced ``*.ms`` of the scope (an elementwise
scope whose time stands far above its operands' bytes moves arrays it need
not: docs/observability.md). Instructions inside ``while`` bodies and
conditionals are not counted (their trips are not in the text).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from horovod_tpu.monitor import hlo_owners  # noqa: E402  (imports no JAX)

#: Instructions that move no data of their own.
FREE = frozenset({"bitcast", "tuple", "get-tuple-element", "constant",
                  "parameter"})
ELEMENTWISE, MATMUL, KERNEL = "elementwise", "matmul", "custom-call"


def entry_of(hlo_text: str) -> str:
    """The name of the text's ENTRY computation."""
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            return line.split()[1].lstrip("%")
    raise ValueError("the text has no ENTRY computation")


def _holds(computations, name, opcodes, seen=None) -> bool:
    """Whether computation ``name`` or one it calls holds an opcode."""
    seen = set() if seen is None else seen
    if name in seen:
        return False
    seen.add(name)
    return any(i.opcode in opcodes
               or (i.calls and _holds(computations, i.calls, opcodes, seen))
               for i in computations.get(name, ()))


def kind_of(computations, inst) -> str:
    if inst.opcode == "custom-call" or (
            inst.calls and _holds(computations, inst.calls, {"custom-call"})):
        return KERNEL
    if inst.opcode in ("dot", "convolution") or (
            inst.calls and _holds(computations, inst.calls,
                                  {"dot", "convolution"})):
        return MATMUL
    return ELEMENTWISE


def moved(hlo_text: str) -> list:
    """``[(instruction, kind, (owner, direction), bytes)]`` of the entry
    computation's instructions that move data."""
    computations = hlo_owners.parse(hlo_text)
    entry = computations[entry_of(hlo_text)]
    shapes = {i.name: i.shape for i in entry}
    out = []
    for inst in entry:
        if inst.opcode in FREE:
            continue
        size = hlo_owners.result_bytes(inst.shape) + sum(
            hlo_owners.result_bytes(shapes.get(o, "")) for o in inst.operands)
        out.append((inst, kind_of(computations, inst),
                    hlo_owners.key_of(inst.path), size))
    return out


def by_scope(rows) -> dict:
    """``{(owner, direction): {kind: [instructions, bytes]}}``."""
    table = defaultdict(lambda: defaultdict(lambda: [0, 0.0]))
    for _, kind, key, size in rows:
        cell = table[key][kind]
        cell[0] += 1
        cell[1] += size
    return table


def compiled_text(workload: str) -> str:
    """The cell's step compiled for a described v5e:2x2, kernels and all
    (``benchmarks/rehearse_compile.py``'s way)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import importlib

    import jax
    from jax.experimental import topologies

    from benchmarks.lib import manifest as mf

    manifest = mf.load()
    cell = mf.cell(manifest, workload)
    config = mf.config_of(manifest, cell["config"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in ("flash_attention", "softmax_xent", "layer_norm"):
        importlib.import_module(
            f"horovod_tpu.ops.{name}")._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    session = mf.load_module("builders", config["builder"]).build(
        config, mf.job_of(cell["traffic"]), topo.devices[:cell["chips"]])
    return session.lower(session.abstract_args()).compile().as_text()


def hbm_rate() -> float:
    with open(os.path.join(ROOT, "benchmarks", "lib", "peaks.json")) as f:
        return json.load(f)["TPU v5 lite"]["hbm_bytes_per_s"]


def report(rows, scope=None, out=sys.stdout) -> None:
    rate = hbm_rate()
    table = by_scope(rows)
    owners = sorted({owner for owner, _ in table},
                    key=lambda o: -sum(table[k][ELEMENTWISE][1]
                                       for k in table if k[0] == o))
    print(f"{'scope':28s} {'direction':9s} {'instr':>6s} {'GB':>8s} "
          f"{'ms at HBM rate':>15s} {'matmul instr/GB':>16s} "
          f"{'kernel instr/GB':>16s}", file=out)
    for owner in owners:
        total = [0, 0.0]
        for direction in (hlo_owners.FORWARD, hlo_owners.REMAT,
                          hlo_owners.BACKWARD):
            kinds = table.get((owner, direction))
            if not kinds:
                continue
            n, size = kinds[ELEMENTWISE]
            total[0] += n
            total[1] += size
            print(f"{owner:28s} {direction:9s} {n:6d} {size / 1e9:8.3f} "
                  f"{size / rate * 1e3:15.2f} "
                  f"{kinds[MATMUL][0]:6d}/{kinds[MATMUL][1] / 1e9:<8.3f} "
                  f"{kinds[KERNEL][0]:6d}/{kinds[KERNEL][1] / 1e9:<8.3f}",
                  file=out)
        print(f"{owner:28s} {'all':9s} {total[0]:6d} {total[1] / 1e9:8.3f} "
              f"{total[1] / rate * 1e3:15.2f}", file=out)
    if scope is None:
        return
    print(f"\n{scope}: instructions outside matmuls and kernels, by bytes",
          file=out)
    mine = [r for r in rows if r[2][0] == scope and r[1] == ELEMENTWISE]
    for inst, _, (_, direction), size in sorted(mine, key=lambda r: -r[3]):
        print(f"{size / 1e6:10.1f} MB {direction:9s} {inst.opcode:12s} "
              f"{inst.name:48s} {inst.shape}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scope", default=None,
                    help="list this scope's instructions by bytes")
    ap.add_argument("--text", default=None,
                    help="a compiled text to read, or where to keep it")
    args = ap.parse_args(argv)
    if args.text and os.path.exists(args.text):
        with open(args.text) as f:
            text = f.read()
    else:
        text = compiled_text(args.workload)
        if args.text:
            with open(args.text, "w") as f:
                f.write(text)
    report(moved(text), args.scope)
    return 0


if __name__ == "__main__":
    sys.exit(main())
