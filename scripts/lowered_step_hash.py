#!/usr/bin/env python3
"""Lower cells' steps for a described v5e, with no chip, and print a hash of
each text: whether a change to shared code moves an accepted cell's program.

    JAX_PLATFORMS=cpu python3 scripts/lowered_step_hash.py <checkout> <cell> [<cell> ...]

Run it on the parent's tree (``git archive`` into a scratch directory) and
on the change's and compare the lines: the same hash is the same StableHLO,
so the same compiled step. A Pallas kernel's ``backend_config`` payload
embeds the source path of the checkout it was traced in and is cut out
before hashing (a kernel's own change shows in its tests and in
``scripts/*_kernel_times.py``, not here). Lowering only: 4 to 7 s a cell,
nothing is compiled or run; several at once need
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` in the sandbox, never on the chip.
"""

from __future__ import annotations

import hashlib
import importlib
import os
import re
import sys
import time

PAYLOAD = re.compile(r'backend_config = "(?:[^"\\]|\\.)*"')


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    root, cells = os.path.abspath(argv[0]), argv[1:]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, root)
    os.chdir(root)

    import jax
    from jax.experimental import topologies

    from benchmarks.lib import manifest as mf

    manifest = mf.load(root)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # The process's backend is the CPU, where the kernels would choose
    # their interpreter; the step is lowered for the described chip.
    for name in ("flash_attention", "softmax_xent", "layer_norm"):
        importlib.import_module(
            f"horovod_tpu.ops.{name}")._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    for name in cells:
        cell = mf.cell(manifest, name)
        config = mf.config_of(manifest, cell["config"], root)
        t0 = time.perf_counter()
        session = mf.load_module("builders", config["builder"]).build(
            config, mf.job_of(cell["traffic"], root),
            topo.devices[:cell["chips"]])
        text = PAYLOAD.sub("backend_config=X", session.lower(
            session.abstract_args()).as_text())
        print(name, hashlib.sha256(text.encode()).hexdigest()[:16],
              len(text), f"{time.perf_counter() - t0:.0f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
