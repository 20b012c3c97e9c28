"""Trace-time wire accounting + overlap-stream instrumentation.

This is the instrumentation half of the plan compiler: every lowered leg
accounts the bytes it puts on each link class at TRACE time (collectives
are traced once per compile, so static per-step byte counts cost nothing
at runtime), and every overlap-scheduled bucket collective is bracketed
with an ``OVERLAP:*`` timeline span plus per-bucket byte/latency
histograms. Because the lowering rules live in ONE place
(:mod:`horovod_tpu.plan.compiler`), every plan is instrumented for free —
no per-path bookkeeping to forget.

The cost model is per-device bytes SENT under ring/topology-aware
schedules: reduce-scatter or all-gather of n elements over k ranks moves
``n*(k-1)/k``, a full allreduce ``2*n*(k-1)/k``; a flat psum over the
mesh axes is modeled as XLA's topology-aware decomposition (ICI leg on
the full payload, DCN leg on the 1/local_size shard, pod leg on the
1/(local*cross) shard). ``dcn_bytes_fp`` tracks what the SAME traffic
pattern would cost at the payload's uncompressed dtype, so
``dcn_bytes_fp / dcn_bytes`` is the wire-representation reduction of the
quantized path (EQuARX's "~4x wire bytes" accounting).

Public surface is re-exported through ``ops.collective_ops``
(``record_wire_stats``/``WireStats``) for compatibility.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

from ..common import basics
from ..monitor import registry as _metrics


class WireStats:
    """Accumulated per-device wire bytes for one traced program."""

    def __init__(self) -> None:
        self.ici_bytes = 0.0
        self.dcn_bytes = 0.0
        self.dcn_bytes_fp = 0.0
        # Cross-POD hop bytes — DCN-class wire physically, but its own
        # link class so 3-level meshes can model an asymmetric pod
        # bandwidth (HOROVOD_BENCH_POD_GBPS) instead of the uniform-DCN
        # assumption (docs/wire-plan.md).
        self.pod_bytes = 0.0
        self.pod_bytes_fp = 0.0
        # Bytes issued through the overlap stream schedule (the
        # allreduce_stream / reduce_scatter_stream / all_gather_stream
        # entry points, docs/overlap.md) — wire traffic positioned so the
        # latency-hiding scheduler can run it under independent compute.
        self.overlap_bytes = 0.0
        self.streamed_buckets = 0
        # Pipeline wire (docs/pipeline.md): bytes moved by send legs —
        # the inter-stage activation/activation-grad ppermutes of the
        # hvd_pp axis. Counted ON TOP of the per-hop ici/dcn/pod totals
        # (a send leg charges both), so the pipeline's share of each
        # link class is separable. ``pp_sends`` counts ppermute issues
        # (schedule ticks x directions).
        self.pp_bytes = 0.0
        self.pp_bytes_fp = 0.0
        self.pp_sends = 0
        # MoE wire (docs/moe.md): bytes moved by a2a legs — the expert
        # dispatch/combine row exchanges of the hvd_ep axis. Same
        # double-charging discipline as the pipeline wire: an a2a leg
        # charges its hop's per-hop total AND these counters, so the
        # MoE share of each link class is separable. ``a2a_calls``
        # counts exchange issues (layers x directions).
        self.a2a_bytes = 0.0
        self.a2a_bytes_fp = 0.0
        self.a2a_calls = 0
        # T3-style pipeline-bubble filling (docs/pipeline.md): bytes of
        # streamed bucket collectives issued inside a ``bubble_fill``
        # window — ZeRO-3 forward-order gathers / grad reduce-scatters
        # positioned so the latency-hiding scheduler runs them in the
        # schedule's idle ticks. A subset of ``overlap_bytes`` (a filled
        # flight is still overlap-scheduled); ``filled_ticks`` counts
        # how many of the schedule's idle ticks took a flight, capped at
        # the PPSchedule's per-rank idle-tick capacity.
        self.bubble_hidden_bytes = 0.0
        self.filled_ticks = 0
        # Serving KV-migration wire (docs/serving.md): bytes moved by
        # kv_migrate send legs — prefill→decode page handoffs between
        # replica groups. Same double-charging discipline as the
        # pipeline/MoE wires: a migration charges its hop's per-hop
        # total AND these counters, so the handoff share of each link
        # class is separable. ``kv_transfers`` counts whole-slot
        # migrations (not chunks).
        self.kv_bytes = 0.0
        self.kv_bytes_fp = 0.0
        self.kv_transfers = 0

    @property
    def dcn_reduction(self) -> Optional[float]:
        """fp-equivalent / actual bytes on the DCN hop (None if no DCN)."""
        return (self.dcn_bytes_fp / self.dcn_bytes) if self.dcn_bytes else None

    @property
    def hidden_fraction(self) -> float:
        """Fraction of this program's wire bytes issued through the
        overlap stream schedule (0.0 with overlap off; collectives
        outside the gradient bucket wire — loss allreduce, batch-stats —
        keep it below 1.0). Published as the ``comm.wire.hidden_fraction``
        gauge; ``scripts/obs_report.py`` recomputes it from the bytes."""
        total = self.ici_bytes + self.dcn_bytes + self.pod_bytes
        return (self.overlap_bytes / total) if total else 0.0


_wire_recorders: list = []


def _acct_enabled() -> bool:
    """Wire accounting is live: an explicit ``record_wire_stats`` recorder
    is installed, or the metrics registry (enabled by default,
    docs/observability.md) is counting trace-time wire bytes. Still a
    trace-time-only cost — nothing here runs in the compiled step."""
    return bool(_wire_recorders) or _metrics.metrics_enabled()


@contextlib.contextmanager
def record_wire_stats():
    """Record wire bytes of every collective traced inside the context.
    Trace-time only: wrap ``jit(...).lower(...)`` (or the first call), not
    the steady-state execution loop. On exit the recorded profile is also
    published to the metrics registry (``comm.wire.*`` gauges — the last
    traced program's per-device wire bytes, hidden fraction included)."""
    ws = WireStats()
    _wire_recorders.append(ws)
    try:
        yield ws
    finally:
        _wire_recorders.remove(ws)
        _publish_wire_stats(ws)


def _publish_wire_stats(ws: "WireStats") -> None:
    if not _metrics.metrics_enabled():
        return
    r = _metrics.default_registry()
    r.counter("comm.traces").inc()
    r.gauge("comm.wire.ici_bytes").set(ws.ici_bytes)
    r.gauge("comm.wire.dcn_bytes").set(ws.dcn_bytes)
    r.gauge("comm.wire.dcn_bytes_fp").set(ws.dcn_bytes_fp)
    r.gauge("comm.wire.pod_bytes").set(ws.pod_bytes)
    r.gauge("comm.wire.overlap_bytes").set(ws.overlap_bytes)
    r.gauge("comm.wire.streamed_buckets").set(ws.streamed_buckets)
    r.gauge("comm.wire.hidden_fraction").set(ws.hidden_fraction)
    r.gauge("comm.wire.pp_bytes").set(ws.pp_bytes)
    r.gauge("comm.wire.pp_sends").set(ws.pp_sends)
    r.gauge("comm.wire.bubble_hidden_bytes").set(ws.bubble_hidden_bytes)
    r.gauge("comm.wire.filled_ticks").set(ws.filled_ticks)
    r.gauge("comm.wire.a2a_bytes").set(ws.a2a_bytes)
    r.gauge("comm.wire.a2a_calls").set(ws.a2a_calls)
    r.gauge("comm.wire.kv_bytes").set(ws.kv_bytes)
    r.gauge("comm.wire.kv_transfers").set(ws.kv_transfers)


def _acct(kind: str, wire_bytes: float, fp_bytes: Optional[float] = None):
    """Account ``wire_bytes`` per-device bytes on one link class.
    ``kind`` is ``"ici"`` for intra-host links, ``"dcn"`` for the
    cross-host hop, ``"pod"`` for the cross-pod hop of a 3-level mesh
    (DCN-class wire physically, but modeled at its own bandwidth)."""
    if _metrics.metrics_enabled():
        _metrics.counter("comm.bytes", hop=kind).inc(wire_bytes)
        if kind in ("dcn", "pod"):
            _metrics.counter("comm.bytes_fp_equiv", hop=kind).inc(
                wire_bytes if fp_bytes is None else fp_bytes)
    for ws in _wire_recorders:
        if kind == "dcn":
            ws.dcn_bytes += wire_bytes
            ws.dcn_bytes_fp += wire_bytes if fp_bytes is None else fp_bytes
        elif kind == "pod":
            ws.pod_bytes += wire_bytes
            ws.pod_bytes_fp += wire_bytes if fp_bytes is None else fp_bytes
        else:
            ws.ici_bytes += wire_bytes


def bench_gbps() -> tuple:
    """(ici, dcn, pod) modeled link bandwidths in GB/s — the
    HOROVOD_BENCH_{ICI,DCN,POD}_GBPS knobs behind every modeled-time
    number (``modeled_wire_ms``, the cost model's static defaults:
    docs/cost-model.md). The pod knob defaults to the DCN value, so
    2-level meshes and unset-knob runs behave exactly as before."""
    ici = float(os.environ.get("HOROVOD_BENCH_ICI_GBPS", "100"))
    dcn = float(os.environ.get("HOROVOD_BENCH_DCN_GBPS", "25"))
    pod = float(os.environ.get("HOROVOD_BENCH_POD_GBPS", str(dcn)))
    return ici, dcn, pod


def modeled_wire_ms(ici_bytes: float, dcn_bytes: float,
                    pod_bytes: float = 0.0) -> float:
    """Modeled transfer time of a payload at the (env-overridable)
    HOROVOD_BENCH_ICI_GBPS/DCN_GBPS/POD_GBPS link bandwidths of
    :func:`bench_gbps`. On the compiled path this
    is the only per-bucket latency that exists at trace time (XLA owns the
    runtime schedule); the eager path measures wall time instead. Applied
    to a :class:`WireStats` record this is the "measured" side of the
    cost-model drift gate (docs/cost-model.md): what the traced program's
    actual wire bytes cost at the modeled bandwidths."""
    ici, dcn, pod = bench_gbps()
    return (ici_bytes / (ici * 1e9) + dcn_bytes / (dcn * 1e9)
            + pod_bytes / (pod * 1e9)) * 1e3


# Back-compat private alias (pre-cost-model spelling).
_modeled_wire_ms = modeled_wire_ms


# Active bubble-fill windows (docs/pipeline.md): a stack because
# nesting is legal (an inner window narrows the budget). Each entry is
# a mutable dict: remaining fill capacity in ticks, flights credited,
# bytes credited, and the window's label.
_fill_windows: list = []


@contextlib.contextmanager
def bubble_fill(capacity_ticks: int, kind: str = "zero3"):
    """T3-style pipeline-bubble fill window (docs/pipeline.md).

    While the window is active, every streamed bucket collective that
    closes (:func:`overlap_stream` — the ZeRO-3 forward-order
    ``all_gather_stream`` flights, the grad reduce-scatter flights) is
    ADDITIONALLY credited as bubble-filled: one flight consumes one of
    the schedule's idle ticks (``PPSchedule.idle_ticks_per_rank`` — the
    fill capacity is rank-uniform by construction), its bytes land on
    ``WireStats.bubble_hidden_bytes``, and the ``comm.pp.filled_ticks``
    / ``comm.pp.bubble_hidden_bytes`` counters bump. Flights beyond the
    capacity get NO credit — the bubble cannot hide more flights than
    it has ticks.

    Trace-time only, like all accounting here: the wrapped collectives
    are issued uniformly on every rank (SPMD collectives cannot be
    per-rank-conditional), positioned adjacent to the schedule scan so
    the latency-hiding scheduler runs them in the idle ticks; this
    window is the accounting contract that prices the placement.
    Yields the window record so callers can read ``filled``/``bytes``.
    """
    tl = basics._state.timeline if basics.is_initialized() else None
    activity = "PP:FILL"
    win = {"remaining": max(0, int(capacity_ticks)), "filled": 0,
           "bytes": 0.0, "kind": str(kind)}
    _fill_windows.append(win)
    if tl is not None:
        tl.begin("pp", activity)
    try:
        yield win
    finally:
        _fill_windows.remove(win)
        if tl is not None:
            tl.end("pp", activity)


def _credit_bubble_fill(delta: float, outer: list) -> None:
    """One streamed flight closed under an active fill window: consume
    an idle tick and credit its bytes as bubble-hidden (every window on
    the stack narrows independently, so nested budgets both count)."""
    credited = False
    for win in _fill_windows:
        if win["remaining"] > 0:
            win["remaining"] -= 1
            win["filled"] += 1
            win["bytes"] += delta
            credited = True
            if _metrics.metrics_enabled():
                _metrics.counter("comm.pp.filled_ticks",
                                 kind=win["kind"]).inc()
                _metrics.counter("comm.pp.bubble_hidden_bytes",
                                 kind=win["kind"]).inc(delta)
    if credited:
        for ws in outer:
            ws.bubble_hidden_bytes += delta
            ws.filled_ticks += 1


@contextlib.contextmanager
def overlap_stream(kind: str, bucket_id):
    """Bracket one streamed bucket collective: emit an ``OVERLAP:<kind>``
    timeline span (host trace time), account the bytes the wrapped
    collective records as overlap-scheduled, and feed the per-bucket
    bytes / modeled-latency histograms of the metrics registry. Inside
    an active :func:`bubble_fill` window the closing flight is also
    credited against the pipeline bubble's idle-tick budget."""
    tl = basics._state.timeline if basics.is_initialized() else None
    tid = f"bucket{bucket_id}"
    activity = f"OVERLAP:{kind}"
    own = WireStats()  # this bucket's bytes, recorder-independent
    _wire_recorders.append(own)
    outer = [ws for ws in _wire_recorders if ws is not own]
    if tl is not None:
        tl.begin(tid, activity)
    try:
        yield
    finally:
        _wire_recorders.remove(own)
        delta = own.ici_bytes + own.dcn_bytes + own.pod_bytes
        for ws in outer:
            ws.overlap_bytes += delta
            ws.streamed_buckets += 1
        if _fill_windows:
            _credit_bubble_fill(delta, outer)
        if _metrics.metrics_enabled():
            r = _metrics.default_registry()
            r.counter("comm.streamed_buckets", kind=kind).inc()
            r.histogram("comm.bucket.bytes").observe(delta)
        if tl is not None:
            tl.end(tid, activity)


def _acct_pp(hop: str, wire_bytes: float, fp_bytes: Optional[float] = None,
             sends: int = 1) -> None:
    """Account a pipeline send leg: charges ``wire_bytes`` to the ``hop``
    link class exactly like any other leg (so ``comm.bytes{hop}`` and
    the per-hop WireStats totals include it), and ADDITIONALLY to the
    pipeline's own counters so a report can separate the inter-stage
    wire from the gradient wire (docs/pipeline.md)."""
    _acct(hop, wire_bytes, fp_bytes)
    if _metrics.metrics_enabled():
        _metrics.counter("comm.pp.bytes", hop=hop).inc(wire_bytes)
        _metrics.counter("comm.pp.sends", hop=hop).inc(sends)
    for ws in _wire_recorders:
        ws.pp_bytes += wire_bytes
        ws.pp_bytes_fp += wire_bytes if fp_bytes is None else fp_bytes
        ws.pp_sends += sends


def _acct_a2a(hop: str, wire_bytes: float,
              fp_bytes: Optional[float] = None, calls: int = 1) -> None:
    """Account a MoE a2a leg: charges ``wire_bytes`` to the ``hop`` link
    class exactly like any other leg (so ``comm.bytes{hop}`` and the
    per-hop WireStats totals include it), and ADDITIONALLY to the MoE
    wire's own counters so a report can separate the expert
    dispatch/combine traffic from the gradient wire (docs/moe.md)."""
    _acct(hop, wire_bytes, fp_bytes)
    if _metrics.metrics_enabled():
        _metrics.counter("comm.moe.bytes", hop=hop).inc(wire_bytes)
        _metrics.counter("comm.moe.calls", hop=hop).inc(calls)
    for ws in _wire_recorders:
        ws.a2a_bytes += wire_bytes
        ws.a2a_bytes_fp += wire_bytes if fp_bytes is None else fp_bytes
        ws.a2a_calls += calls


def _acct_kv(hop: str, wire_bytes: float,
             fp_bytes: Optional[float] = None,
             transfers: int = 0) -> None:
    """Account a KV-migration send leg: charges ``wire_bytes`` to the
    ``hop`` link class exactly like any other leg (so
    ``comm.bytes{hop}`` and the per-hop WireStats totals include it),
    and ADDITIONALLY to the serving handoff's own counters so a report
    can separate prefill→decode migration traffic from the training and
    pipeline wires (docs/serving.md). ``transfers`` bumps only when a
    whole slot finished migrating — chunked transfers charge bytes per
    chunk but one transfer per slot."""
    _acct(hop, wire_bytes, fp_bytes)
    if _metrics.metrics_enabled():
        _metrics.counter("comm.kv.bytes", hop=hop).inc(wire_bytes)
    for ws in _wire_recorders:
        ws.kv_bytes += wire_bytes
        ws.kv_bytes_fp += wire_bytes if fp_bytes is None else fp_bytes
        ws.kv_transfers += transfers


@contextlib.contextmanager
def kv_span(kind: str = "MIGRATE", tid: str = "serve"):
    """Bracket one KV-handoff wire event in a ``SERVE:KV_<kind>``
    timeline span (kinds today: ``MIGRATE`` — one chunk of a
    prefill→decode page transfer crossing the wire). Host-time span:
    unlike the trace-time collective spans, migrations run eagerly
    between engine steps (docs/serving.md)."""
    tl = basics._state.timeline if basics.is_initialized() else None
    activity = f"SERVE:KV_{kind}"
    if tl is not None:
        tl.begin(tid, activity)
    try:
        yield
    finally:
        if tl is not None:
            tl.end(tid, activity)


@contextlib.contextmanager
def moe_span(kind: str, tid: str = "moe"):
    """Bracket one MoE wire event in a ``MOE:<kind>`` timeline span
    (kinds today: ``DISPATCH`` — the token→expert a2a exchange;
    ``COMBINE`` — the expert→token return exchange). Trace-time only,
    like every span here (docs/moe.md)."""
    tl = basics._state.timeline if basics.is_initialized() else None
    activity = f"MOE:{kind}"
    if tl is not None:
        tl.begin(tid, activity)
    try:
        yield
    finally:
        if tl is not None:
            tl.end(tid, activity)


@contextlib.contextmanager
def pp_span(kind: str, tid: str = "pp"):
    """Bracket one pipeline event in a ``PP:<kind>`` timeline span
    (kinds today: ``SEND`` — one lowered send leg; ``F``/``B`` — a
    schedule slot's forward/backward chunk, emitted per rank by
    :func:`emit_schedule_spans`). Trace-time only, like every span
    here."""
    tl = basics._state.timeline if basics.is_initialized() else None
    activity = f"PP:{kind}"
    if tl is not None:
        tl.begin(tid, activity)
    try:
        yield
    finally:
        if tl is not None:
            tl.end(tid, activity)
