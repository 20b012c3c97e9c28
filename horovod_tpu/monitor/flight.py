"""Flight recorder: crash-forensic ring buffer of framework events.

The monitor package's *live* pillars (registry, StallInspector, sinks)
tell you what a healthy run is doing; this module is the forensic pillar
— the artifact you autopsy after a rank crashed, hung, or was killed.

Design (docs/observability.md):

* **Always-on bounded ring.** A ``deque(maxlen=HOROVOD_FLIGHT_RECORDER_
  EVENTS)`` (default 4096, ``0`` disables) of recent framework events:
  every Timeline event is tapped in (spans, instants, counters), and the
  forensically-critical sources record directly so the ring works even
  with no Timeline attached — fault counters (``FAULT:*``), stall
  instants, eager collectives (``FLIGHT:COLLECTIVE``), step/commit marks
  (``FLIGHT:STEP``/``FLIGHT:COMMIT``), serve engine steps
  (``FLIGHT:SERVE_STEP``). A compact registry snapshot is folded in every
  ``HOROVOD_FLIGHT_SNAPSHOT_EVERY`` events (default 1024), so a dump
  carries metric history, not just the final state. Appending one event
  is a lock + deque append — the armed-forensics overhead budget is <1%
  of a representative step (tests/test_monitor.py::TestOverhead).

* **Atomic dumps.** ``dump(reason)`` serializes the ring + a full
  registry snapshot + the StallInspector's in-flight set + the straggler
  history to ``HOROVOD_FLIGHT_RECORDER_DIR`` with the checkpoint layout's
  write discipline (docs/checkpoint.md): tmp file beside the target, one
  ``os.replace`` commit, and a crc32 of the canonical event payload in
  the header so ``scripts/postmortem.py`` can reject torn files.

* **Dump triggers.** Armed by ``hvd.init()`` when the dir knob is set:
  unhandled exceptions (``sys.excepthook`` chain), SIGTERM (dump, then
  re-deliver so exit semantics are preserved), native crashes
  (``faulthandler`` tracebacks land beside the dumps), StallInspector
  escalation past the shutdown deadline, the elastic worker's
  reset-on-peer-failure and the elastic driver's abandon-incarnation
  paths, a chaos ``crash`` injection (the injector dumps before
  ``os._exit`` — a kernel-panic simulation still leaves its black box),
  and the explicit ``hvd.dump_flight_record()`` API.

Stdlib-only, like :mod:`.registry`: the launcher/driver processes record
and dump too; the one framework lookup (rank identity) is lazy and
guarded.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
import zlib
from typing import Dict, List, Optional

DEFAULT_EVENTS = 4096
DEFAULT_SNAPSHOT_EVERY = 1024
DUMP_VERSION = 1
DUMP_PREFIX = "flight_"


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v in (None, ""):
        return default
    try:
        return int(v)
    except ValueError:
        return default


def _identity() -> Dict[str, object]:
    """Best-effort rank identity, resolvable from any process (worker,
    launcher, driver) at any lifecycle point — including mid-teardown."""
    ident: Dict[str, object] = {
        "pid": os.getpid(),
        "hostname": os.environ.get("HOROVOD_HOSTNAME") or "",
        "local_rank": os.environ.get("HOROVOD_LOCAL_RANK") or "",
        "rank": -1,
        "world": 0,
    }
    try:
        from ..common import basics

        if basics.is_initialized():
            ident["rank"] = int(basics.rank())
            ident["world"] = int(basics.size())
            return ident
    except Exception:
        pass
    env_rank = os.environ.get("HOROVOD_RANK")
    if env_rank not in (None, ""):
        try:
            ident["rank"] = int(env_rank)
        except ValueError:
            pass
    env_size = os.environ.get("HOROVOD_SIZE")
    if env_size not in (None, ""):
        try:
            ident["world"] = int(env_size)
        except ValueError:
            pass
    if ident["rank"] == -1 and not ident["hostname"]:
        ident["role"] = "driver"
    return ident


_EXPERT_KEY_RE = None  # compiled lazily (re import stays cold-path)


def _extract_expert_load(registry_snap: Optional[dict]) -> Dict[str, float]:
    """Fold the per-expert load metrics of a registry snapshot into a
    compact ``{expert_id: tokens}`` dict: ``serve.expert_tokens{expert}``
    histogram sums (the serving engines) plus
    ``moe.expert_tokens{expert}`` counters (a training loop's)."""
    if not registry_snap:
        return {}
    global _EXPERT_KEY_RE
    if _EXPERT_KEY_RE is None:
        import re

        _EXPERT_KEY_RE = re.compile(
            r"^(?:serve|moe)\.expert_tokens\{expert=(\d+)\}$")
    load: Dict[str, float] = {}
    for key, h in (registry_snap.get("histograms") or {}).items():
        m = _EXPERT_KEY_RE.match(key)
        if m and isinstance(h, dict):
            e = m.group(1)
            load[e] = load.get(e, 0.0) + float(h.get("sum", 0.0))
    for key, v in (registry_snap.get("counters") or {}).items():
        m = _EXPERT_KEY_RE.match(key)
        if m:
            e = m.group(1)
            load[e] = load.get(e, 0.0) + float(v)
    return load


#: Registry keys folded into the dump's compact ``serve_cache`` view
#: (docs/serving.md): the disaggregated-serving health triple — prefix
#: cache effectiveness, speculative acceptance, and KV-migration wire
#: state — so scripts/postmortem.py can name a migration-stalled
#: replica or a cold prefix cache without walking the raw registry.
_SERVE_CACHE_GAUGES = (
    "serve.prefix_lookups", "serve.prefix_hits",
    "serve.prefix_hit_tokens", "serve.prefix_hit_rate",
    "serve.prefix_cached_pages", "serve.spec.acceptance_rate",
    "serve.prefill_replicas", "serve.decode_replicas",
)
_SERVE_CACHE_COUNTERS = (
    "serve.spec.proposed", "serve.spec.accepted",
    "serve.prefill_handoffs", "serve.kv.migrations",
    "serve.kv.migrations_in", "serve.kv.stall_steps",
)


def _extract_serve_cache(registry_snap: Optional[dict]) -> dict:
    """The disaggregated-serving view of a registry snapshot: flat
    prefix/speculation/migration scalars, per-hop ``comm.kv.bytes``,
    and the per-replica stall attribution
    (``serve.kv.stall_steps_by{replica}``)."""
    if not registry_snap:
        return {}
    gauges = registry_snap.get("gauges") or {}
    counters = registry_snap.get("counters") or {}
    view: dict = {}
    for key in _SERVE_CACHE_GAUGES:
        if key in gauges:
            view[key] = float(gauges[key])
    for key in _SERVE_CACHE_COUNTERS:
        if key in counters:
            view[key] = float(counters[key])
    kv_bytes: Dict[str, float] = {}
    stall_by: Dict[str, float] = {}
    for key, v in counters.items():
        if key.startswith("comm.kv.bytes{hop="):
            kv_bytes[key[len("comm.kv.bytes{hop="):-1]] = float(v)
        elif key.startswith("serve.kv.stall_steps_by{replica="):
            stall_by[key[len("serve.kv.stall_steps_by{replica="):-1]] = \
                float(v)
    if kv_bytes:
        view["kv_bytes"] = kv_bytes
    if stall_by:
        view["stall_steps_by_replica"] = stall_by
    return view


class FlightRecorder:
    """Bounded in-memory ring of recent framework events."""

    def __init__(self, capacity: Optional[int] = None,
                 snapshot_every: Optional[int] = None) -> None:
        if capacity is None:
            capacity = _env_int("HOROVOD_FLIGHT_RECORDER_EVENTS",
                                DEFAULT_EVENTS)
        if snapshot_every is None:
            snapshot_every = _env_int("HOROVOD_FLIGHT_SNAPSHOT_EVERY",
                                      DEFAULT_SNAPSHOT_EVERY)
        self.capacity = max(0, int(capacity))
        self.snapshot_every = max(0, int(snapshot_every))
        self._lock = threading.Lock()
        self._ring: "collections.deque" = collections.deque(
            maxlen=self.capacity or 1)
        self._seq = 0
        self._since_snapshot = 0
        self._dump_seq = 0

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    # -- recording (any thread) ----------------------------------------

    def record(self, name: str, ph: str = "i", *, tid: str = "main",
               ts: Optional[float] = None,
               args: Optional[dict] = None) -> None:
        """Append one event. ``ts`` is the emitter's own clock (the
        Timeline's relative µs for tapped events); every entry also gets
        a wall-clock stamp so dumps from different ranks join on one
        axis."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": ph, "tid": tid, "wall": time.time()}
        if ts is not None:
            ev["ts"] = ts
        if args:
            ev["args"] = args
        with self._lock:
            ev["seq"] = self._seq
            self._seq += 1
            self._ring.append(ev)
            self._since_snapshot += 1
            take_snap = (self.snapshot_every > 0
                         and self._since_snapshot >= self.snapshot_every)
            if take_snap:
                self._since_snapshot = 0
        if take_snap:
            self._record_registry_snapshot()

    def tap(self, ev: dict) -> None:
        """Mirror one Timeline event into the ring (called from
        ``Timeline.emit``). Copies — the writer thread serializes the
        original dict and must not see the wall/seq stamps."""
        if not self.enabled:
            return
        self.record(str(ev.get("name", "")), str(ev.get("ph", "i")),
                    tid=str(ev.get("tid", "main")), ts=ev.get("ts"),
                    args=ev.get("args"))

    def _record_registry_snapshot(self) -> None:
        try:
            from . import registry as _registry

            snap = _registry.default_registry().snapshot()
        except Exception:
            return
        ev = {"name": "FLIGHT:SNAPSHOT", "ph": "i", "tid": "flight",
              "wall": time.time(),
              "args": {"counters": snap["counters"],
                       "gauges": snap["gauges"]}}
        with self._lock:
            ev["seq"] = self._seq
            self._seq += 1
            self._ring.append(ev)

    def mark_step(self, step, phases: Optional[dict] = None) -> None:
        """Record one completed training step (the marker
        ``scripts/postmortem.py`` derives the last-common-step and the
        divergence point from)."""
        args: Dict[str, object] = {}
        if step is not None:
            args["step"] = int(step)
        if phases:
            args["phases_ms"] = {k: round(float(v), 3)
                                 for k, v in phases.items()}
        self.record("FLIGHT:STEP", tid="flight", args=args)

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._seq = 0
            self._since_snapshot = 0

    # -- dumping --------------------------------------------------------

    def build_dump(self, reason: str,
                   extra: Optional[dict] = None) -> dict:
        """The dump payload: ring + registry + in-flight ops + straggler
        history, crc32-stamped over the canonical event serialization."""
        events = self.events()
        registry_snap: Optional[dict] = None
        try:
            from . import registry as _registry

            registry_snap = _registry.default_registry().snapshot()
        except Exception:
            pass
        in_flight: List[str] = []
        stalled: List[dict] = []
        try:
            from . import stall as _stall

            insp = _stall.stall_inspector()
            in_flight = insp.in_flight()
            stalled = insp.stalled()
        except Exception:
            pass
        straggler_history: List[dict] = []
        try:
            from . import straggler as _straggler

            straggler_history = _straggler.straggler_detector().history()
        except Exception:
            pass
        expert_load = _extract_expert_load(registry_snap)
        serve_cache = _extract_serve_cache(registry_snap)
        payload = json.dumps(events, sort_keys=True).encode()
        dump = {
            "version": DUMP_VERSION,
            "kind": "flight_record",
            "reason": reason,
            "ts": time.time(),
            "identity": _identity(),
            "events": events,
            "events_crc32": f"crc32:{zlib.crc32(payload) & 0xFFFFFFFF:08x}",
            "registry": registry_snap,
            "in_flight": in_flight,
            "stalled": stalled,
            "straggler": straggler_history,
        }
        if expert_load:
            # Per-expert load (docs/moe.md): the compact {expert: tokens}
            # view of the serve.expert_tokens/moe.expert_tokens metrics,
            # so scripts/postmortem.py can name a hot expert without
            # re-deriving it from raw histogram buckets.
            dump["expert_load"] = expert_load
        if serve_cache:
            # Disaggregated-serving health (docs/serving.md): compact
            # prefix-cache / speculative-acceptance / KV-migration view,
            # including the per-replica stall attribution postmortem
            # uses to name a migration-stalled replica.
            dump["serve_cache"] = serve_cache
        if extra:
            dump["extra"] = extra
        return dump

    def dump(self, reason: str = "explicit", *,
             path: Optional[str] = None,
             directory: Optional[str] = None,
             extra: Optional[dict] = None) -> Optional[str]:
        """Write one dump atomically (tmp → ``os.replace``). Returns the
        committed path, or None when recording is disabled or no
        destination is configured (``path`` > ``directory`` >
        ``HOROVOD_FLIGHT_RECORDER_DIR``). Never raises: the dump runs on
        crash paths where a second failure must not mask the first."""
        if not self.enabled:
            return None
        try:
            if path is None:
                directory = directory or os.environ.get(
                    "HOROVOD_FLIGHT_RECORDER_DIR") or None
                if not directory:
                    return None
                os.makedirs(directory, exist_ok=True)
                ident = _identity()
                tag = (f"rank{ident['rank']}" if ident["rank"] >= 0
                       else (f"{ident['hostname']}-{ident['local_rank']}"
                             if ident["hostname"] else "driver"))
                with self._lock:
                    seq = self._dump_seq
                    self._dump_seq += 1
                path = os.path.join(
                    directory,
                    f"{DUMP_PREFIX}{tag}_pid{os.getpid()}_{seq:03d}.json")
            dump = self.build_dump(reason, extra=extra)
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(dump, f, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            return path
        except Exception:
            return None


# ---------------------------------------------------------------------------
# Process-global recorder + module-level recording shortcuts (what the
# framework call sites use — cheap no-ops when the ring is disabled).
# ---------------------------------------------------------------------------

_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def recorder() -> FlightRecorder:
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = FlightRecorder()
    return _recorder


#: Package-level accessor name (``hvd.monitor.flight_recorder()``).
def flight_recorder() -> FlightRecorder:
    return recorder()


def record(name: str, ph: str = "i", *, tid: str = "main",
           ts: Optional[float] = None, args: Optional[dict] = None) -> None:
    recorder().record(name, ph, tid=tid, ts=ts, args=args)


def instant(name: str, *, tid: str = "main",
            args: Optional[dict] = None) -> None:
    recorder().record(name, "i", tid=tid, args=args)


def tap(ev: dict) -> None:
    recorder().tap(ev)


def mark_step(step, phases: Optional[dict] = None) -> None:
    recorder().mark_step(step, phases)


def dump_flight_record(path: Optional[str] = None,
                       reason: str = "explicit",
                       extra: Optional[dict] = None) -> Optional[str]:
    """Dump the flight record now (``hvd.dump_flight_record()``). With no
    ``path`` the dump lands in ``HOROVOD_FLIGHT_RECORDER_DIR`` (None is
    returned when neither is set)."""
    return recorder().dump(reason, path=path, extra=extra)


def _reset_for_tests() -> None:
    global _recorder, _armed
    with _recorder_lock:
        _recorder = None
    _armed = False
    with _sigterm_hook_lock:
        del _sigterm_hooks[:]


# ---------------------------------------------------------------------------
# Crash-path arming: excepthook chain, SIGTERM, faulthandler. Installed
# once per process by lifecycle.start_from_env() when the dump dir is
# configured (there is nowhere to dump otherwise).
# ---------------------------------------------------------------------------

_armed = False
_prev_excepthook = None
_prev_sigterm = None
_faulthandler_file = None

# Callables run (each guarded) at the TOP of the SIGTERM handler, before
# the checkpoint-writer drain and the flight dump. The resilience
# supervisor registers its deadline-budgeted priority snapshot here: the
# ordering contract is snapshot → drain → dump → re-deliver, so the
# flight record includes the snapshot's RESILIENCE:PREEMPT event and the
# process never dies holding a torn half-written commit.
_sigterm_hooks: list = []
_sigterm_hook_lock = threading.Lock()


def register_sigterm_hook(fn) -> None:
    """Run ``fn()`` on SIGTERM before the flight dump (idempotent)."""
    with _sigterm_hook_lock:
        if fn not in _sigterm_hooks:
            _sigterm_hooks.append(fn)


def unregister_sigterm_hook(fn) -> None:
    with _sigterm_hook_lock:
        if fn in _sigterm_hooks:
            _sigterm_hooks.remove(fn)


def _flight_excepthook(exc_type, exc, tb):
    try:
        recorder().dump("exception", extra={
            "exc_type": getattr(exc_type, "__name__", str(exc_type)),
            "exc": str(exc)[:500]})
    except Exception:
        pass
    hook = _prev_excepthook or sys.__excepthook__
    hook(exc_type, exc, tb)


def _flight_sigterm(signum, frame):
    import signal

    # 1. Pre-dump hooks (e.g. the supervisor's priority snapshot) — each
    #    guarded so one bad hook can't cost the dump or the drain.
    with _sigterm_hook_lock:
        hooks = list(_sigterm_hooks)
    for fn in hooks:
        try:
            fn()
        except Exception:
            pass
    # 2. Quiesce in-flight checkpoint commits: an AsyncWriter caught
    #    mid-write must land its manifest before we re-deliver the
    #    signal, or the grace window ends with a torn commit the restore
    #    path would silently skip. Budgeted — a wedged disk can't eat
    #    the whole grace period.
    try:
        from ..checkpoint import writer as _ckpt_writer

        budget = float(os.environ.get(
            "HOROVOD_SIGTERM_DRAIN_SECS", "10"))
        _ckpt_writer.drain_all(timeout=budget)
    except Exception:
        pass
    # 3. The black box itself.
    try:
        recorder().dump("sigterm")
    except Exception:
        pass
    # Preserve delivery semantics: restore whatever handler we displaced
    # and re-raise, so the process still dies of SIGTERM (exit 143) — or
    # runs the application's own handler — exactly as before arming.
    prev = _prev_sigterm
    if callable(prev):
        prev(signum, frame)
        return
    signal.signal(signal.SIGTERM,
                  prev if prev is not None else signal.SIG_DFL)
    os.kill(os.getpid(), signal.SIGTERM)


def arm(directory: Optional[str] = None) -> bool:
    """Install the crash-path dump handlers (idempotent). Returns True
    when armed. ``directory`` overrides HOROVOD_FLIGHT_RECORDER_DIR for
    the faulthandler sidecar file; the dump destination itself is
    resolved per dump."""
    global _armed, _prev_excepthook, _prev_sigterm, _faulthandler_file
    directory = directory or os.environ.get(
        "HOROVOD_FLIGHT_RECORDER_DIR") or None
    if not directory or not recorder().enabled:
        return False
    if _armed:
        return True
    _armed = True
    os.makedirs(directory, exist_ok=True)
    _prev_excepthook = sys.excepthook
    sys.excepthook = _flight_excepthook
    # faulthandler: a native crash (SIGSEGV/SIGABRT) cannot run Python,
    # but its traceback can still land beside the dumps.
    try:
        import faulthandler

        ident = _identity()
        tag = (f"rank{ident['rank']}" if ident["rank"] >= 0
               else f"pid{os.getpid()}")
        _faulthandler_file = open(
            os.path.join(directory, f"fault_{tag}_pid{os.getpid()}.txt"),
            "w")
        faulthandler.enable(file=_faulthandler_file)
    except Exception:
        pass
    # SIGTERM: main-thread only (signal module restriction); a worker
    # being preempted/killed still leaves its black box.
    try:
        import signal

        if threading.current_thread() is threading.main_thread():
            _prev_sigterm = signal.getsignal(signal.SIGTERM)
            signal.signal(signal.SIGTERM, _flight_sigterm)
    except Exception:
        pass
    return True


def arm_from_env(config=None) -> bool:
    """lifecycle.start_from_env entry: arm when a dump dir is configured
    (Config.flight_recorder_dir / HOROVOD_FLIGHT_RECORDER_DIR)."""
    directory = None
    if config is not None:
        directory = getattr(config, "flight_recorder_dir", None)
    return arm(directory)
