"""Overlapped-collective scheduling flags (docs/overlap.md).

XLA hides collectives under compute only when (a) the collective lowers
to an async start/done pair and (b) the latency-hiding scheduler is
allowed to stretch the start→done window across independent compute.
Both are TPU compiler flags, and the TPU compiler lives in libtpu, which
reads them from ``LIBTPU_INIT_ARGS`` when it initializes. ``XLA_FLAGS``
is the wrong carrier: jaxlib parses it too, knows none of these
spellings, and aborts the process (``Unknown flags in XLA_FLAGS``) —
checked against jaxlib 0.9.0 / libtpu 0.0.34, which accept all six
through ``LIBTPU_INIT_ARGS`` and reject a misspelt one there.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

import jax

# The canonical TPU async-collective + LHS flag set (the same knobs the
# public MaxText/T5X configs ship with).
_OVERLAP_XLA_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
)


def _requested_platform() -> str:
    """The platform the process is headed for, WITHOUT creating a backend
    (libtpu reads its flags once, when the first backend comes up):
    jax.config's jax_platforms if set, else the JAX_PLATFORMS env, else
    'auto'."""
    p = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS") or ""
    p = p.split(",")[0].strip().lower()
    return p or "auto"


def _backend_already_created() -> bool:
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def enable_overlap_scheduling(platform: Optional[str] = None) -> bool:
    """Arm the async-collective + latency-hiding-scheduler flags for the
    overlapped gradient reduction (``HOROVOD_OVERLAP=1``, docs/overlap.md).

    Appends :data:`_OVERLAP_XLA_FLAGS` to ``LIBTPU_INIT_ARGS`` so the
    NEXT PJRT client creation compiles collectives as async start/done
    pairs the scheduler can stretch over independent backward compute.
    Returns True when the flags are in place for a TPU backend.

    Only libtpu reads that variable, so ``auto`` arms it without having
    to guess whether a chip is attached: on a host without one the flags
    are never parsed. A process pinned to another platform is a logged
    no-op — the overlap *schedule* (stream-ordered buckets,
    double-buffered microbatches, ops/fusion.py) still traces
    identically; only the compiler-level hiding is absent. Call before
    the first ``jax.devices()``; once a backend exists the flags cannot
    take effect in this process and we say so instead of silently lying.
    """
    platform = (platform or _requested_platform()).lower()
    if platform not in ("auto", "tpu"):
        _log(f"overlap: platform {platform!r} has no async-collective "
             "flag support; running the overlap schedule without "
             "compiler-level latency hiding")
        return False
    args = os.environ.get("LIBTPU_INIT_ARGS", "")
    missing = [f for f in _OVERLAP_XLA_FLAGS if f not in args]
    if not missing:
        return True
    if _backend_already_created():
        _log("overlap: the XLA backend is already initialized; async-"
             "collective flags cannot apply to this process (set "
             "HOROVOD_OVERLAP=1 before the first jax.devices() call, or "
             "export LIBTPU_INIT_ARGS yourself)")
        return False
    os.environ["LIBTPU_INIT_ARGS"] = (args + " " + " ".join(missing)).strip()
    _log("overlap: armed async-collective/latency-hiding flags in "
         f"LIBTPU_INIT_ARGS ({len(missing)} added)")
    return True


def _log(msg: str) -> None:
    print(f"[horovod_tpu] {msg}", file=sys.stderr, flush=True)
