#!/usr/bin/env python3
"""Compile a cell's step for a DESCRIBED v5e:2x2, with no chip attached.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py --workload <cell>

Run by hand before a chip call (guide on-chip-measurement, section 2,
rehearsal 3): what the TPU compiler refuses here costs no chip time. Prints
``memory_analysis()``, the ``tpu_custom_call`` count and the collectives in
the compiled text. Nothing runs, so it says nothing about results or times,
and a compile that passes is never reported as a chip run. Never imported
by a test at import time: it describes a topology, which loads the TPU's
library into the process.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)

    import jax
    from jax.experimental import topologies

    from benchmarks.lib import manifest as mf
    import importlib

    manifest = mf.load()
    cell = mf.cell(manifest, args.workload)
    config = mf.config_of(manifest, cell["config"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # The process's backend is the CPU, where the kernels would choose
    # their interpreter; the step is compiled for the described chip.
    for name in ("flash_attention", "softmax_xent", "layer_norm"):
        importlib.import_module(
            f"horovod_tpu.ops.{name}")._interpret = lambda: False
    # A compile for a described chip cannot be read back from the cache.
    jax.config.update("jax_enable_compilation_cache", False)

    session = mf.load_module("builders", config["builder"]).build(
        config, mf.job_of(cell["traffic"]), topo.devices[:cell["chips"]])
    t0 = time.perf_counter()
    compiled = session.lower(session.abstract_args()).compile()
    print(f"[rehearse] {args.workload}: compiled for {cell['chips']} "
          f"described v5e chip(s) in {time.perf_counter() - t0:.1f} s "
          f"(sandbox CPU seconds, not a device number)")
    session.compiled = compiled
    mem = session.memory_analysis()
    print(f"[rehearse] memory per device: {mem} = "
          f"{mem['total'] / 1e9:.2f} GB of 16")
    text = compiled.as_text()
    print(f"[rehearse] tpu_custom_call: {text.count('tpu_custom_call')}")
    found = re.findall(
        r"\b(all-reduce|reduce-scatter|all-gather|all-to-all|"
        r"collective-permute)(?:-start)?\(", text)
    print(f"[rehearse] collectives: "
          f"{ {k: found.count(k) for k in sorted(set(found))} }")
    return 0


if __name__ == "__main__":
    sys.exit(main())
