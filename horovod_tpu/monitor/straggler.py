"""Cross-rank straggler attribution: per-phase skew + link-health scores.

The MPI-characterization line of work (PAPERS.md: profiling-driven
per-phase/per-link behavior) and T3's transparent tracking both show that
per-step attribution is cheap enough to leave on. This module is that
attribution for the monitor layer: every rank records how long each
*phase* of its step took —

======================  ====================================================
``compute``              step time not attributable to wire/ckpt/bubble
``wire.ici``             intra-host collective wire (modeled on the
                         compiled path, measured on the eager path)
``wire.dcn``             cross-host wire (eager collectives charge here:
                         the process-world data plane is DCN-class TCP)
``wire.pod``             cross-pod wire of a 3-level mesh
``pp_bubble``            pipeline idle (bubble fraction × step)
``ckpt``                 checkpoint save stall visible to the trainer
======================  ====================================================

— as ``straggler.phase_ms{phase,rank}`` gauges where each rank writes
ONLY its own rank's entries (all ranks pre-create the full matrix, so
every rank's registry schema is identical). The values therefore ride
the registry's existing ONE-fused-allreduce aggregation unchanged: a SUM
over ranks reconstructs the full per-rank matrix, because every other
rank contributed zero. No second collective, no new wire protocol.

:meth:`StragglerDetector.detect` runs median/MAD outlier detection over
that matrix per phase and, for each outlier, emits a rank-and-phase-
attributed diagnosis: a ``straggler.detected{rank,phase}`` counter, a
``step.skew_ms{phase}`` gauge (max − median), a ``STRAGGLER:<PHASE>``
timeline/flight instant, and a history entry that rides the flight dump
(docs/observability.md).

**Link health** closes the loop with the PR-11 cost model: every
``observe_wire(hop, bytes, measured_ms)`` scores the hop as measured /
predicted wire-ms for *this rank's* traffic (``plan/cost``'s resolved —
calibrated-else-static — model). A persistent one-rank drift (EWMA above
``HOROVOD_LINK_DRIFT_GATE`` for ``patience`` consecutive observations)
flags a degraded link: ``straggler.link_degraded{hop}`` counter,
``link.health{hop}`` gauge, a ``STRAGGLER:LINK_DEGRADED`` instant, and a
log line recommending a :func:`~horovod_tpu.plan.calibrate.
calibrate_links` recalibration (docs/cost-model.md).

Stdlib-only at import, like the registry; the cost-model lookup is lazy
and never raises into the step.
"""

from __future__ import annotations

import collections
import logging
import os
import re
import threading
import time
from typing import Dict, List, Optional

from . import flight as _flight
from . import registry as _registry

logger = logging.getLogger("horovod_tpu.straggler")

#: Canonical phase vocabulary (docs/observability.md). record_phase
#: accepts any name, but detection/reporting tables order these first.
#: ``wire.a2a`` is the MoE dispatch/combine wire (docs/moe.md) — a
#: training loop records it so a straggling expert group attributes to
#: its exchange phase, separate from the gradient wire's hop classes.
#: ``wire.kv`` is disaggregated serving's KV-migration wire
#: (docs/serving.md) — a replica stuck in it is blocked on a
#: prefill→decode handoff, not on compute. ``compile`` is
#: lowering+XLA-compile time paid through the executable cache
#: (docs/compile.md) — a rank stuck there missed the cache others hit.
PHASES = ("compute", "wire.ici", "wire.dcn", "wire.pod", "wire.a2a",
          "wire.kv", "pp_bubble", "ckpt", "compile")

HOPS = ("ici", "dcn", "pod")

#: Consistency scale: MAD × 1.4826 estimates the standard deviation of a
#: normal distribution, so the gate is in familiar sigma units.
MAD_SIGMA = 1.4826

_PHASE_KEY_RE = re.compile(
    r"^straggler\.phase_ms\{phase=([^,}]+),rank=(\d+)\}$")
_STEPS_KEY_RE = re.compile(r"^straggler\.steps\{rank=(\d+)\}$")


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v in (None, ""):
        return default
    try:
        return float(v)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v in (None, ""):
        return default
    try:
        return int(v)
    except ValueError:
        return default


def _world_and_rank():
    try:
        from ..common import basics

        if basics.is_initialized():
            return int(basics.size()), int(basics.rank())
    except Exception:
        pass
    return 1, 0


def _timeline_instant(name: str, args: dict) -> None:
    """STRAGGLER:* instants go to the Timeline when one is attached (the
    flight ring taps it there) and straight to the flight ring when not —
    the forensic trail exists either way."""
    tl = None
    try:
        from ..common import basics

        tl = basics._state.timeline
    except Exception:
        pass
    if tl is not None:
        tl.instant(name, tid="stragglers", args=args)
    else:
        _flight.instant(name, tid="stragglers", args=args)


class StragglerDetector:
    """Per-rank phase recording + cross-rank median/MAD detection.

    ``mad_gate`` is the outlier threshold in MAD-sigmas above the
    cross-rank median; ``min_skew_ms`` is the absolute floor below which
    skew is never flagged (guards the MAD≈0 case of near-identical
    ranks); ``link_drift_gate`` is the measured/predicted wire-ms ratio
    past which a hop counts as drifting; ``patience`` consecutive
    drifting observations flag it degraded.
    """

    def __init__(self, registry: Optional[_registry.MetricsRegistry] = None,
                 *, world: Optional[int] = None, rank: Optional[int] = None,
                 mad_gate: Optional[float] = None,
                 min_skew_ms: Optional[float] = None,
                 link_drift_gate: Optional[float] = None,
                 patience: Optional[int] = None,
                 history_len: int = 256) -> None:
        self._registry = registry or _registry.default_registry()
        self._world_override = world
        self._rank_override = rank
        self.mad_gate = (_env_float("HOROVOD_STRAGGLER_MAD_GATE", 4.0)
                         if mad_gate is None else float(mad_gate))
        self.min_skew_ms = (_env_float("HOROVOD_STRAGGLER_MIN_SKEW_MS", 5.0)
                            if min_skew_ms is None else float(min_skew_ms))
        self.link_drift_gate = (
            _env_float("HOROVOD_LINK_DRIFT_GATE", 1.5)
            if link_drift_gate is None else float(link_drift_gate))
        self.patience = (_env_int("HOROVOD_LINK_DRIFT_PATIENCE", 3)
                         if patience is None else int(patience))
        self._lock = threading.Lock()
        self._current: Dict[str, float] = {}
        self._step = 0
        self._history: "collections.deque" = collections.deque(
            maxlen=history_len)
        # Link health: per-hop EWMA of measured/predicted + consecutive
        # over-gate observations + degraded latch (warn once per latch).
        self._link_ewma: Dict[str, float] = {}
        self._link_over: Dict[str, int] = {}
        self._link_degraded: Dict[str, bool] = {}

    def _world_rank(self):
        world, rank = _world_and_rank()
        if self._world_override is not None:
            world = self._world_override
        if self._rank_override is not None:
            rank = self._rank_override
        return max(1, int(world)), int(rank)

    # -- per-step phase recording (this rank) ---------------------------

    def record_phase(self, phase: str, ms: float) -> None:
        """Accumulate ``ms`` into the current step's ``phase`` bucket."""
        if ms < 0:
            ms = 0.0
        with self._lock:
            self._current[phase] = self._current.get(phase, 0.0) + float(ms)

    def end_step(self, step: Optional[int] = None) -> Dict[str, float]:
        """Close the current step: publish this rank's phase durations as
        the rank-slotted gauges (pre-creating every rank's slot so all
        registries share one aggregation schema), bump
        ``straggler.steps{rank}``, and mark the step in the flight ring.
        Returns the phase dict."""
        with self._lock:
            phases = dict(self._current)
            self._current.clear()
            step = self._step if step is None else int(step)
            self._step = step + 1
        world, rank = self._world_rank()
        reg = self._registry
        for phase in set(PHASES) | set(phases):
            for r in range(world):
                g = reg.gauge("straggler.phase_ms", phase=phase,
                              rank=str(r))
                if r == rank:
                    g.set(phases.get(phase, 0.0))
        for r in range(world):
            c = reg.counter("straggler.steps", rank=str(r))
            if r == rank:
                c.inc()
        _flight.mark_step(step, phases)
        return phases

    # -- cross-rank detection -------------------------------------------

    @staticmethod
    def _matrix(snapshot: dict):
        """(rank → phase → ms, set of live ranks) from an (aggregated)
        registry snapshot."""
        matrix: Dict[int, Dict[str, float]] = {}
        for key, v in snapshot.get("gauges", {}).items():
            m = _PHASE_KEY_RE.match(key)
            if m:
                phase, r = m.group(1), int(m.group(2))
                matrix.setdefault(r, {})[phase] = float(v)
        live = set()
        for key, v in snapshot.get("counters", {}).items():
            m = _STEPS_KEY_RE.match(key)
            if m and v > 0:
                live.add(int(m.group(1)))
        return matrix, live

    def detect(self, snapshot: Optional[dict] = None,
               aggregate: bool = True) -> List[dict]:
        """One detection pass over the last completed step.

        With no ``snapshot`` the per-rank matrix comes from the
        registry's own fused-allreduce aggregation filtered to the
        straggler family (identity in a world of one); pass the
        reporter's already-aggregated full snapshot to fold detection
        into the existing interval allreduce at zero extra wire. Emits
        the attributed counters/gauges/instants for every outlier and
        returns them."""
        if snapshot is None:
            snapshot = (self._registry.aggregate(prefix="straggler.")
                        if aggregate
                        else self._registry.snapshot(prefix="straggler."))
        matrix, live = self._matrix(snapshot)
        ranks = sorted(r for r in matrix if r in live) if live \
            else sorted(matrix)
        detections: List[dict] = []
        if len(ranks) < 3:
            # With fewer than 3 ranks a median/MAD split cannot name an
            # outlier without guessing; skew gauges still publish below.
            pass
        phases = sorted({p for r in ranks for p in matrix.get(r, {})})
        reg = self._registry
        for phase in phases:
            vals = [matrix[r].get(phase, 0.0) for r in ranks]
            if not vals:
                continue
            med = _median(vals)
            skew = max(vals) - med
            reg.gauge("step.skew_ms", phase=phase).set(skew)
            if len(ranks) < 3:
                continue
            mad = _median([abs(v - med) for v in vals])
            gate = med + max(self.mad_gate * MAD_SIGMA * mad,
                             self.min_skew_ms)
            for r, v in zip(ranks, vals):
                if v <= gate:
                    continue
                det = {"kind": "phase", "rank": r, "phase": phase,
                       "ms": round(v, 3), "median_ms": round(med, 3),
                       "mad_ms": round(mad, 3), "skew_ms": round(v - med, 3),
                       "ts": time.time()}
                detections.append(det)
                reg.counter("straggler.detected", rank=str(r),
                            phase=phase).inc()
                _timeline_instant(
                    f"STRAGGLER:{phase.upper()}",
                    {"rank": r, "phase": phase, "ms": det["ms"],
                     "median_ms": det["median_ms"],
                     "mad_ms": det["mad_ms"]})
                logger.warning(
                    f"straggler detected: rank {r} spent {v:.1f} ms in "
                    f"phase {phase!r} vs cross-rank median {med:.1f} ms "
                    f"(MAD {mad:.1f} ms)")
        with self._lock:
            self._history.extend(detections)
        return detections

    # -- link health ----------------------------------------------------

    def observe_wire(self, hop: str, nbytes: float,
                     measured_ms: float) -> Optional[float]:
        """Score one hop's measured wire time against the cost model's
        prediction for the same traffic. Returns the EWMA ratio (None
        when no prediction is available — pricing must never break the
        step)."""
        if hop not in HOPS or nbytes <= 0 or measured_ms < 0:
            return None
        try:
            from ..plan import cost as _cost

            predicted_ms = _cost.predict_hop_ms(hop, nbytes)
        except Exception:
            return None
        if predicted_ms <= 0:
            return None
        ratio = float(measured_ms) / predicted_ms
        reg = self._registry
        with self._lock:
            prev = self._link_ewma.get(hop)
            ewma = ratio if prev is None else 0.5 * prev + 0.5 * ratio
            self._link_ewma[hop] = ewma
            if ewma > self.link_drift_gate:
                self._link_over[hop] = self._link_over.get(hop, 0) + 1
                recovered = False
            else:
                self._link_over[hop] = 0
                recovered = bool(self._link_degraded.get(hop))
                self._link_degraded[hop] = False
            over = self._link_over[hop]
            newly_degraded = (over >= self.patience
                              and not self._link_degraded.get(hop))
            if newly_degraded:
                self._link_degraded[hop] = True
        reg.gauge("link.health", hop=hop).set(ewma)
        if newly_degraded:
            _, rank = self._world_rank()
            reg.counter("straggler.link_degraded", hop=hop).inc()
            det = {"kind": "link", "rank": rank, "hop": hop,
                   "ratio": round(ewma, 3),
                   "gate": self.link_drift_gate, "ts": time.time()}
            with self._lock:
                self._history.append(det)
            _timeline_instant("STRAGGLER:LINK_DEGRADED",
                              {"rank": rank, "hop": hop,
                               "ratio": det["ratio"],
                               "gate": self.link_drift_gate})
            logger.warning(
                f"link health: {hop} hop measured/predicted wire-ms "
                f"ratio {ewma:.2f} exceeded the drift gate "
                f"{self.link_drift_gate:g} for {over} consecutive "
                f"observations on rank {rank} — the link is degraded or "
                f"the cost model is stale; re-run "
                f"horovod_tpu.plan.calibrate.calibrate_links() to "
                f"recalibrate (docs/cost-model.md)")
        if recovered:
            # The latch cleared: the hop's EWMA dropped back under the
            # gate. The resilience supervisor keys its replan swap-back
            # on this transition.
            reg.counter("straggler.link_recovered", hop=hop).inc()
            _timeline_instant("STRAGGLER:LINK_RECOVERED",
                              {"hop": hop, "ratio": round(ewma, 3),
                               "gate": self.link_drift_gate})
            logger.info(
                f"link health: {hop} hop recovered (EWMA ratio "
                f"{ewma:.2f} back under the gate "
                f"{self.link_drift_gate:g})")
        return ewma

    def link_scores(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._link_ewma)

    def degraded_hops(self) -> Dict[str, float]:
        """{hop: EWMA ratio} for hops whose degraded latch is set."""
        with self._lock:
            return {hop: self._link_ewma.get(hop, 0.0)
                    for hop, flag in self._link_degraded.items() if flag}

    def history(self) -> List[dict]:
        """Detection history (bounded) — rides every flight dump."""
        with self._lock:
            return list(self._history)

    def reset(self) -> None:
        with self._lock:
            self._current.clear()
            self._step = 0
            self._history.clear()
            self._link_ewma.clear()
            self._link_over.clear()
            self._link_degraded.clear()


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


# ---------------------------------------------------------------------------
# Process-global detector (the stall-inspector pattern): framework call
# sites (bench loop, eager collectives, reporter thread) share one.
# ---------------------------------------------------------------------------

_global: Optional[StragglerDetector] = None
_global_lock = threading.Lock()


def straggler_detector() -> StragglerDetector:
    global _global
    if _global is None:
        with _global_lock:
            if _global is None:
                _global = StragglerDetector()
    return _global


def record_phase(phase: str, ms: float) -> None:
    straggler_detector().record_phase(phase, ms)


def end_step(step: Optional[int] = None) -> Dict[str, float]:
    return straggler_detector().end_step(step)


def observe_wire(hop: str, nbytes: float, measured_ms: float):
    return straggler_detector().observe_wire(hop, nbytes, measured_ms)


def record_pp_bubble(idle_ticks: int, ticks: int, step_ms: float,
                     filled_ticks: int = 0,
                     detector: Optional[StragglerDetector] = None) -> float:
    """Attribute this rank's pipeline bubble time to the ``pp_bubble``
    phase (docs/pipeline.md).

    The zero-bubble scheduler exposes its *measured* per-rank idle-tick
    count (``PPSchedule.idle_ticks_per_rank``) and the step loop knows
    how many of those ticks ZeRO-3 flights actually filled
    (``comm.pp.filled_ticks``). A filled tick is wire work hidden in
    the bubble, not lost time, so it must NOT be charged as bubble skew
    — otherwise every rank that successfully overlaps looks like a
    straggler relative to one that could not. This helper charges only
    the *unfilled* remainder::

        ms = step_ms * (idle_ticks - min(idle_ticks, filled_ticks)) / ticks

    On a clean run the schedule's idle ticks are identical across ranks
    (the table is geometry-determined), so the phase is rank-uniform
    and detect() stays silent; genuine cross-rank skew — one rank's
    flights starved so its bubbles went unfilled — surfaces as a
    ``pp_bubble`` outlier with the usual median/MAD gate.

    Returns the charged milliseconds (0.0 when fully filled).
    """
    d = detector or straggler_detector()
    t = max(1, int(ticks))
    idle = max(0, int(idle_ticks))
    filled = min(idle, max(0, int(filled_ticks)))
    ms = float(step_ms) * (idle - filled) / float(t)
    d.record_phase("pp_bubble", ms)
    return ms


def _reset_for_tests() -> None:
    global _global
    with _global_lock:
        _global = None
