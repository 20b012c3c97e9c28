"""The failure-policy supervisor: detection signals → recovery actions.

One :class:`Supervisor` wraps the runtime's existing mechanisms:

* **Preemption** — :meth:`attach` registers a SIGTERM pre-dump hook with
  the flight recorder (``monitor/flight.py``). A spot-style notice
  (real SIGTERM, or the chaos ``preempt`` action) then runs a
  *deadline-budgeted priority snapshot*: the configured snapshot
  provider's state goes through the CheckpointManager's AsyncWriter and
  is drained under ``HOROVOD_PREEMPT_SNAPSHOT_DEADLINE_SECS``, all
  *before* the flight dump re-delivers the signal — so the grace window
  buys a durable commit, and the flight record carries the
  ``RESILIENCE:PREEMPT`` event with the deadline verdict.

* **Restart** — restart-from-last-commit rides the existing
  ``CheckpointedJaxState`` reshard path; the supervisor only meters it:
  :meth:`record_restart` spends from
  ``HOROVOD_RESILIENCE_RESTART_BUDGET`` and the policy engine escalates
  when the budget is gone.

* **Degraded-link replanning** — when the straggler detector's
  link-health latch flags a hop (``observe_wire`` EWMA over the drift
  gate for ``patience`` windows), :meth:`maybe_replan` re-prices the
  PR-11 shortlist under a :class:`~horovod_tpu.plan.cost.CostModel`
  *override* (the hop's bandwidth scaled down by the observed EWMA
  ratio — not a recalibration) and returns the winning quantized-wire
  plan for the trainer to hot-swap at a step boundary. The swap is
  recorded (``RESILIENCE:REPLAN``) and reverses on recovery
  (``RESILIENCE:REPLAN_REVERT``) when the latch clears.

* **Failures generally** — :meth:`on_failure` feeds the
  :class:`~horovod_tpu.resilience.policy.PolicyEngine` and *performs*
  ladder actions it can (blacklist via the driver's HostManager);
  shrink/abort are returned to the caller, who owns the loop.

The supervisor holds no thread of its own: everything runs on the
caller's step boundary or inside the signal handler, which keeps the
ordering contract (snapshot → writer drain → flight dump → re-delivery)
trivially true.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..monitor import registry as _registry
from ..monitor.straggler import _timeline_instant
from . import policy as _policy

logger = logging.getLogger("horovod_tpu.resilience")


@dataclasses.dataclass
class ReplanDecision:
    """One recorded degraded-link replan (or its recovery revert)."""

    hop: str
    ewma_ratio: float          # measured/predicted at decision time
    plan_before: Optional[str]  # canonical encoding (None = knob default)
    plan_after: Optional[str]
    predicted_ms: float        # winner's prediction under the override
    reverted: bool = False     # set on the matching swap-back
    step: Optional[int] = None

    def as_dict(self) -> dict:
        return {"hop": self.hop,
                "ewma_ratio": round(self.ewma_ratio, 3),
                "plan_before": self.plan_before,
                "plan_after": self.plan_after,
                "predicted_ms": round(self.predicted_ms, 6),
                "reverted": self.reverted, "step": self.step}


class Supervisor:
    """Wraps ElasticDriver + CheckpointManager behind the policy layer.

    All collaborators are optional so the pieces compose à la carte:
    a serve-only job attaches with no driver, a unit test with neither.

    ``snapshot_provider`` is a zero-argument callable returning
    ``(step, tree, extra)`` — the state a preemption-notice priority
    snapshot should commit — or None when there is nothing newer than
    the last commit.
    """

    def __init__(self,
                 driver=None,
                 ckpt_manager=None,
                 snapshot_provider:
                 Optional[Callable[[], Optional[Tuple[int, dict,
                                                      Optional[dict]]]]]
                 = None,
                 engine: Optional[_policy.PolicyEngine] = None,
                 straggler=None,
                 registry: Optional[_registry.MetricsRegistry] = None,
                 snapshot_deadline_secs: Optional[float] = None,
                 restart_budget: Optional[int] = None,
                 readmission_probe:
                 Optional[Callable[[str], bool]] = None) -> None:
        self.driver = driver
        self.ckpt_manager = ckpt_manager
        self._snapshot_provider = snapshot_provider
        self.engine = engine or _policy.PolicyEngine(registry=registry)
        self._straggler = straggler
        self._registry = registry or _registry.default_registry()
        if snapshot_deadline_secs is None:
            snapshot_deadline_secs = _env_float(
                "HOROVOD_PREEMPT_SNAPSHOT_DEADLINE_SECS", 5.0)
        self.snapshot_deadline_secs = float(snapshot_deadline_secs)
        if restart_budget is None:
            restart_budget = _env_int(
                "HOROVOD_RESILIENCE_RESTART_BUDGET", 3)
        self.restart_budget = int(restart_budget)
        self._restarts = 0
        self._lock = threading.Lock()
        self._attached = False
        self._gate = _policy.ReadmissionGate(
            probe=readmission_probe, registry=self._registry)
        # Degraded-link replanning state: one active swap per hop.
        self._active_swaps: Dict[str, ReplanDecision] = {}
        self._replans: List[ReplanDecision] = []
        self._preempt_log: List[dict] = []

    # -- lifecycle -------------------------------------------------------

    def attach(self) -> "Supervisor":
        """Register the SIGTERM priority-snapshot hook (before the
        flight dump; see monitor/flight.py ordering contract) and the
        readmission gate on the driver's HostManager. Idempotent."""
        if self._attached:
            return self
        self._attached = True
        try:
            from ..monitor import flight as _flight

            _flight.register_sigterm_hook(self._on_preemption)
        except Exception:
            pass
        hm = getattr(self.driver, "host_manager", None)
        if hm is not None:
            try:
                hm.set_readmission_probe(self._gate)
            except Exception:
                pass
        _timeline_instant("RESILIENCE:ATTACH",
                          {"deadline_secs": self.snapshot_deadline_secs,
                           "restart_budget": self.restart_budget})
        return self

    def detach(self) -> None:
        if not self._attached:
            return
        self._attached = False
        try:
            from ..monitor import flight as _flight

            _flight.unregister_sigterm_hook(self._on_preemption)
        except Exception:
            pass
        hm = getattr(self.driver, "host_manager", None)
        if hm is not None:
            try:
                hm.set_readmission_probe(None)
            except Exception:
                pass

    def set_snapshot_provider(self, fn) -> None:
        self._snapshot_provider = fn

    # -- preemption ------------------------------------------------------

    def _on_preemption(self) -> None:
        """The SIGTERM pre-dump hook: a deadline-budgeted priority
        snapshot through the AsyncWriter. Never raises (the flight
        handler guards it anyway, but the dump must happen)."""
        try:
            self.on_preemption_notice()
        except Exception as e:
            logger.error(f"resilience: priority snapshot failed: {e!r}")

    def on_preemption_notice(self, source: str = "sigterm") -> dict:
        """Handle one preemption notice; returns the event record."""
        started = time.monotonic()
        deadline = started + self.snapshot_deadline_secs
        decision = self.engine.record_failure(
            _policy.CLASS_PREEMPTION, key=source)
        reg = self._registry
        saved_step = None
        committed = False
        if (self._snapshot_provider is not None
                and self.ckpt_manager is not None):
            snap = None
            try:
                snap = self._snapshot_provider()
            except Exception as e:
                logger.error(
                    f"resilience: snapshot provider failed: {e!r}")
            if snap is not None:
                step, tree, extra = snap
                latest = None
                try:
                    latest = self.ckpt_manager.latest_step()
                except Exception:
                    pass
                if latest is None or step > latest:
                    try:
                        self.ckpt_manager.save(int(step), tree,
                                               extra=extra)
                        saved_step = int(step)
                    except Exception as e:
                        logger.error(
                            f"resilience: priority save failed: {e!r}")
                else:
                    # Nothing newer than the last commit — the drain
                    # below still quiesces any in-flight write.
                    saved_step = latest
            try:
                remaining = max(0.0, deadline - time.monotonic())
                committed = bool(self.ckpt_manager.wait(remaining))
            except Exception:
                committed = False
        elapsed_ms = (time.monotonic() - started) * 1e3
        deadline_met = (committed
                        and elapsed_ms <= self.snapshot_deadline_secs
                        * 1e3)
        if saved_step is None:
            # No state to commit: the notice is still deadline-met as
            # long as we are inside the grace window.
            deadline_met = elapsed_ms <= self.snapshot_deadline_secs * 1e3
        event = {"source": source, "saved_step": saved_step,
                 "committed": committed,
                 "deadline_secs": self.snapshot_deadline_secs,
                 "elapsed_ms": round(elapsed_ms, 3),
                 "deadline_met": deadline_met,
                 "policy_action": decision.action}
        reg.counter("resilience.preempt.snapshots",
                    verdict=("deadline_met" if deadline_met
                             else "deadline_missed")).inc()
        _timeline_instant("RESILIENCE:PREEMPT", event)
        with self._lock:
            self._preempt_log.append(event)
            del self._preempt_log[:-64]
        logger.warning(
            f"resilience: preemption notice ({source}) — priority "
            f"snapshot step={saved_step} committed={committed} in "
            f"{elapsed_ms:.0f} ms (deadline "
            f"{self.snapshot_deadline_secs:g}s, "
            f"{'met' if deadline_met else 'MISSED'})")
        return event

    # -- restart budget --------------------------------------------------

    def restart_allowed(self) -> bool:
        with self._lock:
            return self._restarts < self.restart_budget

    def record_restart(self, restored_step: Optional[int] = None) -> bool:
        """One restart-from-last-commit happened; False = budget gone
        (the caller should treat the next failure as fatal)."""
        with self._lock:
            self._restarts += 1
            n = self._restarts
        self._registry.counter("resilience.restarts").inc()
        _timeline_instant("RESILIENCE:RESTART",
                          {"restored_step": restored_step, "count": n,
                           "budget": self.restart_budget})
        if n > self.restart_budget:
            self.engine.record_failure(_policy.CLASS_WORKER_CRASH,
                                       key="restart_budget")
            return False
        return True

    # -- generic failure routing ----------------------------------------

    def on_failure(self, cls: str, key: str = "*",
                   detail: Optional[dict] = None) -> _policy.Decision:
        """Record a failure; perform the ladder actions the supervisor
        can (blacklist); return the decision for the caller's loop."""
        decision = self.engine.record_failure(cls, key=key, detail=detail)
        if decision.action == _policy.RECOVER_BLACKLIST:
            hm = getattr(self.driver, "host_manager", None)
            if hm is not None and key not in ("*", ""):
                try:
                    hm.blacklist(key)
                except Exception:
                    pass
        return decision

    def on_success(self, cls: str, key: str = "*") -> None:
        self.engine.record_success(cls, key=key)

    # -- degraded-link replanning ---------------------------------------

    def maybe_replan(self, payload_bytes: float, *,
                     mesh_shape=None, compute_ms=None,
                     step: Optional[int] = None) -> Optional[dict]:
        """Step-boundary hook: inspect the link-health latches and
        return a swap directive, a revert directive, or None.

        On a newly degraded hop: re-price the shortlist under the
        EWMA-derated cost model and return ``{"swap": PricedPlan,
        "hop": ..., "decision": ReplanDecision}`` — the caller applies
        the plan (e.g. ``quantized=True`` on its collectives) from the
        next step. On recovery (latch cleared): return
        ``{"revert": True, "hop": ...}``. Never raises into the step.
        """
        det = self._straggler
        if det is None:
            try:
                from ..monitor import straggler as _straggler_mod

                det = _straggler_mod.straggler_detector()
            except Exception:
                return None
        try:
            degraded = det.degraded_hops()
        except Exception:
            return None
        # Recovery first: any active swap whose hop is healthy again.
        for hop in list(self._active_swaps):
            if hop not in degraded:
                rec = self._active_swaps.pop(hop)
                rec.reverted = True
                self._registry.counter("resilience.replans",
                                       kind="revert", hop=hop).inc()
                _timeline_instant("RESILIENCE:REPLAN_REVERT",
                                  {"hop": hop, "step": step,
                                   "plan": rec.plan_after})
                self.engine.record_success(_policy.CLASS_DEGRADED_LINK,
                                           key=hop)
                logger.warning(
                    f"resilience: {hop} link recovered — reverting the "
                    f"quantized-wire swap at step {step}")
                return {"revert": True, "hop": hop, "decision": rec}
        for hop, ewma in degraded.items():
            if hop in self._active_swaps:
                continue  # already swapped; hold until recovery
            decision = self.engine.record_failure(
                _policy.CLASS_DEGRADED_LINK, key=hop,
                detail={"ewma_ratio": round(ewma, 3)})
            if decision.action != _policy.RECOVER_REPLAN:
                continue
            swap = self._price_swap(hop, ewma, payload_bytes,
                                    mesh_shape=mesh_shape,
                                    compute_ms=compute_ms)
            if swap is None:
                continue
            plan_row, rec = swap
            rec.step = step
            with self._lock:
                self._active_swaps[hop] = rec
                self._replans.append(rec)
                del self._replans[:-64]
            self._registry.counter("resilience.replans",
                                   kind="swap", hop=hop).inc()
            _timeline_instant("RESILIENCE:REPLAN", rec.as_dict())
            logger.warning(
                f"resilience: {hop} link degraded (EWMA ratio "
                f"{ewma:.2f}) — hot-swapping to "
                f"{rec.plan_after} at step {step} "
                f"(predicted {rec.predicted_ms:.3f} ms under the "
                f"observed-bandwidth override)")
            return {"swap": plan_row, "hop": hop, "decision": rec}
        return None

    def _price_swap(self, hop: str, ewma: float, payload_bytes: float, *,
                    mesh_shape=None, compute_ms=None):
        """Re-price the shortlist with the hop's bandwidth derated by
        the observed EWMA ratio — a CostModel *override*, not a
        recalibration (the calibration store is untouched)."""
        try:
            from ..plan import cost as _cost
            from ..plan import planner as _planner

            base = _cost.resolve(mesh_shape)
            link = base.link(hop)
            derated = dataclasses.replace(
                link, bandwidth_gbps=max(1e-6,
                                         link.bandwidth_gbps
                                         / max(1.0, ewma)))
            override = dataclasses.replace(
                base, source=f"{base.source}+observed:{hop}",
                **{hop: derated})
            rows = _planner.shortlist(payload_bytes,
                                      mesh_shape=mesh_shape,
                                      model=override,
                                      compute_ms=compute_ms,
                                      quantized=True, k=4)
        except Exception as e:
            logger.warning(
                f"resilience: replan pricing failed for {hop}: {e!r}")
            return None
        if not rows:
            return None
        # Prefer a winner that actually uses the quantized wire on the
        # degraded hop; the top row usually does under the derated
        # bandwidth (int8 moves 4x fewer bytes over the slow link).
        best = None
        for row in rows:
            enc = row.plan.encode()
            if "int8" in enc:
                best = row
                break
        best = best or rows[0]
        before = None
        try:
            baseline = _planner.shortlist(payload_bytes,
                                          mesh_shape=mesh_shape,
                                          quantized=False, k=1)
            if baseline:
                before = baseline[0].plan.encode()
        except Exception:
            pass
        rec = ReplanDecision(hop=hop, ewma_ratio=float(ewma),
                             plan_before=before,
                             plan_after=best.plan.encode(),
                             predicted_ms=float(best.predicted_ms))
        return best, rec

    # -- reporting -------------------------------------------------------

    def active_swaps(self) -> Dict[str, ReplanDecision]:
        with self._lock:
            return dict(self._active_swaps)

    def report(self) -> dict:
        """Supervisor state for the soak report / flight dump."""
        with self._lock:
            replans = [r.as_dict() for r in self._replans]
            preempts = list(self._preempt_log)
            restarts = self._restarts
        return {
            "policy": self.engine.snapshot(),
            "replans": replans,
            "preemptions": preempts,
            "restarts": restarts,
            "restart_budget": self.restart_budget,
            "snapshot_deadline_secs": self.snapshot_deadline_secs,
        }


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
