"""Persistent executable cache: compile once, run everywhere warm.

Horovod's response cache exists so a stable tensor set never re-pays
coordination (reference ``common/response_cache.h``); on the XLA path the
analogous recurring cost is *compilation* — every autotune trial, every
elastic resize, every restarted worker used to re-pay lowering + XLA
compile for programs this process (or a previous one) already built.
This module is the framework-level answer, two layers deep:

1. :func:`arm_persistent_cache` turns on JAX's own persistent
   compilation cache at :func:`cache_dir` — wherever
   ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed directory in the
   checkout — so *any* jit compile in the process can be served from
   disk by XLA itself. Armed from :func:`horovod_tpu.init` BEFORE the
   mesh exists — the knob only applies cleanly ahead of the first
   compilation.
2. :class:`ExecutableCache` — a registry of *loaded executables* keyed by
   ``(tag, plan encoding, mesh_geometry() fingerprint, shape/dtype
   signature, jax version)``. A hit skips lowering AND compile entirely
   (``jax.experimental.serialize_executable`` payloads, pickled beside a
   JSON index with the autotune cache's flock + atomic-replace
   discipline), which is what makes warm bench reruns, autotune replays,
   and restarted elastic workers start in milliseconds.

Observability contract (docs/compile.md): ``compile.hits`` /
``compile.misses`` / ``compile.compile_ms{key=tag}`` metrics,
``COMPILE:LOWER`` / ``COMPILE:COMPILE`` spans + ``COMPILE:CACHE_HIT``
instants on the Timeline (span_audit vocabulary), a ``compile``
straggler phase, and flight-recorder ring entries. Failure discipline
follows ``get_cost_model``: the cache is an optimization, never a
failure — a corrupt index, an unreadable payload, or a deserialize
error logs a warning and falls back to a cold compile.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

logger = logging.getLogger("horovod_tpu.compile")

_lock = threading.Lock()
#: in-memory registry: key -> (compiled, compile_ms, aux)
_mem: Dict[str, Tuple[Any, float, dict]] = {}
#: process-lifetime counters (reset via :func:`reset_stats`)
_stats = {"hits": 0, "misses": 0, "disk_hits": 0, "compile_ms": 0.0}
_warned = {"disk": False, "arm": False}

#: Bump when the on-disk entry layout changes — stale-format entries are
#: ignored (treated as misses), never an error.
_FORMAT_VERSION = 2


# ---------------------------------------------------------------------------
# knobs


def enabled() -> bool:
    """Whether the compile cache (both layers) is armed.

    ``HOROVOD_COMPILE_CACHE=0`` disables persistence entirely; the
    in-memory executable registry stays on (it is what de-duplicates
    identical compiles inside one process)."""
    from ..common.config import _env_bool

    return _env_bool("HOROVOD_COMPILE_CACHE", True)


#: Where the cache lives when nobody placed it: one fixed, git-ignored
#: directory in the checkout. The path is part of JAX's cache key, so a
#: directory that moves between runs (a temp dir, a pid, the time) never
#: hits, and the home directory is not this program's to write.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".compile_cache")


def cache_dir() -> str:
    """Root of everything that is compiled once and kept:
    ``JAX_COMPILATION_CACHE_DIR`` where set, else
    :data:`_CHECKOUT_CACHE_DIR`. JAX's persistent compilation cache
    writes its entries into the root itself; ``exec/`` holds the
    serialized-executable payloads + ``index.json`` and
    ``kernel_autotune.json`` the Pallas block choices those executables
    were compiled with (ops/kernel_autotune.py)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE_DIR


def _exec_dir() -> str:
    return os.path.join(cache_dir(), "exec")


def _index_path() -> str:
    return os.path.join(_exec_dir(), "index.json")


# ---------------------------------------------------------------------------
# persistent XLA compilation cache (layer 1)


def arm_persistent_cache(config=None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache at :func:`cache_dir`.

    Called from ``hvd.init`` before the mesh is built (before any
    compilation — the persistent cache only covers compiles issued after
    arming). Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already
    taken its directory from it and this function names no other; only
    an unplaced cache is pointed at the checkout's. Thresholds are
    zeroed so fast CPU-mesh compiles persist too: the CI smoke and
    warm-rerun gates run on the 2x4 host-platform mesh where every
    compile is "too fast to be worth caching" under JAX's defaults.
    Returns the armed directory, or None when disabled or when arming
    fails (logged once, never raised)."""
    if config is not None and not getattr(config, "compile_cache", True):
        return None
    if not enabled():
        return None
    root = cache_dir()
    try:
        import jax

        os.makedirs(root, exist_ok=True)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", root)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    except Exception as e:  # cache is an optimization, never a failure
        if not _warned["arm"]:
            _warned["arm"] = True
            logger.warning("persistent compilation cache not armed "
                           "(%s: %s) — compiles stay cold across "
                           "processes", type(e).__name__, str(e)[:200])
        return None
    return root


@contextlib.contextmanager
def persistent_cache_disabled():
    """JAX's persistent compilation cache off for the block: a leg that
    must pay a cold compile, or a compile for a described (unattached)
    chip whose entry could never be read back. Off, not re-pointed at a
    throwaway directory — JAX reads its directory once, and the
    directory is part of every entry's key."""
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as _jcc)

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    _jcc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        _jcc.reset_cache()


# ---------------------------------------------------------------------------
# executable keys (layer 2)


def _shapes_signature(shapes) -> str:
    """Stable signature of an abstract-args pytree: per-leaf
    ``shape/dtype`` plus the NamedSharding spec when one is attached
    (two differently-sharded lowers of one fn are different
    executables)."""
    if shapes is None:
        return "noshapes"
    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves(shapes):
        shp = getattr(leaf, "shape", None)
        dt = getattr(leaf, "dtype", None)
        if shp is None and dt is None:
            parts.append(repr(leaf))
            continue
        sig = f"{'x'.join(str(int(s)) for s in shp)}:{dt}"
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        if spec is not None:
            sig += f":{spec}"
        parts.append(sig)
    raw = ";".join(parts)
    if len(raw) > 160:
        raw = hashlib.sha1(raw.encode()).hexdigest()[:16]
    return raw or "noshapes"


def _mesh_fingerprint(mesh) -> str:
    """``<geometry>#<device-ids>``: ``mesh_geometry()`` when the mesh
    fits the framework vocabulary, otherwise (e.g. the serve engine's
    1-D ``serve_tp`` mesh) a raw ``mesh<shape>@<axes>|world<N>|<kind>``
    form. The ids are part of the key because a serialized executable
    carries its device assignment: two replicas over different device
    slices, or a 1-chip mesh and the whole host, are different
    executables."""
    from ..common import basics

    if mesh is None:   # the live framework mesh, if there is one
        mesh = basics._state.mesh if basics.is_initialized() else None
        if mesh is None:
            return "nomesh"
    devs = list(mesh.devices.ravel())
    if len(mesh.devices.shape) >= 2:
        geo = basics.mesh_geometry(mesh=mesh)
    else:
        shape = "x".join(str(int(v)) for v in mesh.devices.shape)
        axes = ".".join(str(a) for a in mesh.axis_names)
        kind = str(getattr(devs[0], "device_kind", "unknown")
                   or "unknown").strip().lower().replace(" ", "-")
        geo = f"mesh{shape}@{axes}|world{len(devs)}|{kind}"
    ids = ",".join(str(d.id) for d in devs)
    if len(ids) > 48:
        ids = hashlib.sha1(ids.encode()).hexdigest()[:12]
    return f"{geo}#{ids}"


def executable_key(tag: str, *, plan: Optional[str] = None,
                   mesh=None, shapes=None,
                   extra: Optional[str] = None) -> str:
    """The registry key for one executable.

    Anatomy (docs/compile.md): ``xc|<tag>|<plan>|<geometry>|<shapes>|
    <extra>|jax<version>|v<format>`` — the wire-plan encoding and the
    ``mesh_geometry()`` fingerprint carry exactly the same
    transfer-safety contract as the autotune warm-start cache: an
    executable compiled for one topology/chip kind/plan never hits
    another."""
    import jax

    geo = _mesh_fingerprint(mesh)
    sig = _shapes_signature(shapes)
    return (f"xc|{tag}|{plan or 'noplan'}|{geo}|{sig}|"
            f"{extra or 'noextra'}|jax{jax.__version__}|"
            f"v{_FORMAT_VERSION}")


# ---------------------------------------------------------------------------
# disk store (flock + atomic replace, kernel_autotune discipline)


def _disk_load(key: str) -> Optional[Tuple[Any, float, dict]]:
    """Deserialize ``key``'s executable from disk onto the devices it was
    compiled for (recorded with the entry); left to itself the loader
    takes every local device and a 1-chip program then wants 8 shards.
    None on any failure (missing, corrupt, incompatible, a device that
    is not here): a logged miss."""
    if not enabled():
        return None
    try:
        with open(_index_path()) as f:
            index = json.load(f)
    except (OSError, json.JSONDecodeError, ValueError):
        return None
    meta = index.get(key) if isinstance(index, dict) else None
    if not isinstance(meta, dict):
        return None
    try:
        import jax
        from jax.experimental import serialize_executable as _se

        by_id = {d.id: d for d in jax.devices()}
        devices = [by_id[i] for i in meta["devices"]]
        with open(os.path.join(_exec_dir(), meta["file"]), "rb") as f:
            payload, in_tree, out_tree = pickle.loads(f.read())
        compiled = _se.deserialize_and_load(
            payload, in_tree, out_tree, execution_devices=devices)
        return (compiled, float(meta.get("compile_ms", 0.0)),
                dict(meta.get("aux") or {}))
    except Exception as e:  # corrupt/foreign entry: cold compile instead
        if not _warned["disk"]:
            _warned["disk"] = True
            logger.warning(
                "executable cache entry unreadable (%s: %s) — falling "
                "back to cold compile; delete %s to clear stale entries",
                type(e).__name__, str(e)[:200], _exec_dir())
        return None


def _disk_store(key: str, compiled, compile_ms: float, aux: dict) -> None:
    """Serialize ``compiled`` beside the index under the OS lock.

    Read-merge-write of ``index.json`` under ``fcntl.flock`` with an
    ``os.replace`` finish — concurrent processes caching different
    executables must not clobber each other (the kernel_autotune store
    discipline)."""
    if not enabled():
        return
    try:
        from jax.experimental import serialize_executable as _se

        payload = pickle.dumps(_se.serialize(compiled))
        devices = [d.id for d in
                   compiled.runtime_executable().local_devices()]
    except Exception as e:  # unserializable backend: memory-only entry
        logger.debug("executable %s not serializable (%s) — memory-only",
                     key, str(e)[:200])
        return
    fname = hashlib.sha1(key.encode()).hexdigest()[:20] + ".bin"
    path = _index_path()
    try:
        os.makedirs(_exec_dir(), exist_ok=True)
        import fcntl

        with open(path + ".lock", "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            tmp_bin = os.path.join(_exec_dir(),
                                   f"{fname}.tmp.{os.getpid()}")
            with open(tmp_bin, "wb") as f:
                f.write(payload)
            os.replace(tmp_bin, os.path.join(_exec_dir(), fname))
            disk: dict = {}
            try:
                with open(path) as f:
                    disk = json.load(f)
            except (FileNotFoundError, json.JSONDecodeError, ValueError):
                pass
            disk[key] = {"file": fname, "devices": devices,
                         "compile_ms": round(float(compile_ms), 3),
                         "aux": aux, "wall": time.time()}
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(disk, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
    except OSError as e:  # cache is an optimization, never a failure
        logger.debug("executable cache write failed: %s", e)


# ---------------------------------------------------------------------------
# observability plumbing


def _timeline():
    from ..common import basics

    return basics._state.timeline if basics.is_initialized() else None


def _span(name: str, ph: str, args: Optional[dict] = None) -> None:
    tl = _timeline()
    if tl is not None:
        tl.emit(name, ph, tid="compile", args=args)
    from ..monitor import flight as _flight

    _flight.record(name, ph, tid="compile", args=args)


def _observe(tag: str, source: str, compile_ms: float, key: str) -> None:
    from ..monitor import registry as _metrics
    from ..monitor import straggler as _straggler

    if source == "compiled":
        _metrics.counter("compile.misses", key=tag).inc()
        _metrics.histogram("compile.compile_ms", key=tag).observe(
            compile_ms)
        _straggler.record_phase("compile", compile_ms)
    else:
        _metrics.counter("compile.hits", key=tag).inc()
        _span("COMPILE:CACHE_HIT", "i",
              {"key": key, "source": source,
               "saved_compile_ms": round(compile_ms, 3)})


# ---------------------------------------------------------------------------
# the registry


@dataclasses.dataclass(frozen=True)
class CompileResult:
    """One :func:`get_or_compile` outcome."""

    compiled: Any          #: the loaded executable (callable)
    source: str            #: ``memory`` | ``disk`` | ``compiled``
    compile_ms: float      #: cost paid (miss) or skipped (hit)
    aux: dict              #: caller metadata persisted with the entry
    key: str               #: the full registry key

    @property
    def cache_hit(self) -> bool:
        return self.source != "compiled"


def get_or_compile(tag: str, lower: Callable[[], Any], *,
                   plan: Optional[str] = None, mesh=None, shapes=None,
                   extra: Optional[str] = None,
                   aux_fn: Optional[Callable[[Any], dict]] = None,
                   ) -> CompileResult:
    """The executable for ``(tag, plan, geometry, shapes)``, compiling at
    most once per key across processes.

    ``lower()`` returns a ``Lowered`` (``jit(fn).lower(*abstract_args)``)
    and only runs on a miss — a memory or disk hit skips lowering AND
    compile. ``aux_fn(lowered)`` (miss only) returns JSON-safe metadata
    persisted with the entry and returned on every later hit: it keeps
    wire-plan byte stats available on warm reruns where no lowering
    happens. Never raises on cache trouble — the worst case is
    a cold compile."""
    key = executable_key(tag, plan=plan, mesh=mesh, shapes=shapes,
                         extra=extra)
    with _lock:
        hit = _mem.get(key)
    if hit is not None:
        compiled, ms, aux = hit
        _observe(tag, "memory", ms, key)
        with _lock:
            _stats["hits"] += 1
        return CompileResult(compiled, "memory", ms, aux, key)

    disk = _disk_load(key)
    if disk is not None:
        compiled, ms, aux = disk
        with _lock:
            _mem[key] = (compiled, ms, aux)
            _stats["hits"] += 1
            _stats["disk_hits"] += 1
        _observe(tag, "disk", ms, key)
        return CompileResult(compiled, "disk", ms, aux, key)

    # Miss: pay lowering + compile, timed as separate spans so the phase
    # breakdown distinguishes trace-heavy from XLA-heavy programs.
    t0 = time.perf_counter()
    _span("COMPILE:LOWER", "B", {"key": key})
    try:
        lowered = lower()
    finally:
        _span("COMPILE:LOWER", "E")
    _span("COMPILE:COMPILE", "B", {"key": key})
    try:
        compiled = lowered.compile()
    finally:
        _span("COMPILE:COMPILE", "E")
    compile_ms = (time.perf_counter() - t0) * 1e3
    aux = {}
    if aux_fn is not None:
        try:
            aux = dict(aux_fn(lowered) or {})
        except Exception as e:  # aux is metadata, never a failure
            logger.debug("aux_fn for %s failed: %s", tag, e)
    with _lock:
        _mem[key] = (compiled, compile_ms, aux)
        _stats["misses"] += 1
        _stats["compile_ms"] += compile_ms
    _observe(tag, "compiled", compile_ms, key)
    _disk_store(key, compiled, compile_ms, aux)
    return CompileResult(compiled, "compiled", compile_ms, aux, key)


# ---------------------------------------------------------------------------
# stats


def stats() -> dict:
    """Process-lifetime registry counters: ``hits`` / ``misses`` (true
    compiles) / ``disk_hits`` / ``compile_ms`` total."""
    with _lock:
        return dict(_stats)


def compile_count() -> int:
    """Number of TRUE compiles this process paid through the registry —
    zero in a process that found every executable on disk
    (tests/test_compile.py holds a second process to that)."""
    with _lock:
        return int(_stats["misses"])


def reset_stats() -> None:
    with _lock:
        _stats.update(hits=0, misses=0, disk_hits=0, compile_ms=0.0)


def clear_memory() -> None:
    """Drop the in-process registry (tests; disk entries survive)."""
    with _lock:
        _mem.clear()
