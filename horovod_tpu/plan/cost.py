"""Analytic plan pricing: predicted wire milliseconds for any legal plan.

HiCCL (arXiv:2408.05962) prices a hierarchical collective analytically
from per-link ``(bandwidth, latency)`` parameters instead of measuring
every composition; EQuARX (arXiv:2506.17615) shows the quantize-rate
tradeoff is itself a priceable term (compression buys wire bytes at the
cost of quantize/dequantize kernel time). This module is that model for
the wire-plan IR: every link class carries a measured

    ``(bandwidth_gbps, latency_us, quant_rate_gbps)``

triple — static env defaults (the ``HOROVOD_BENCH_*_GBPS`` knobs every
modeled-time number already uses), or a calibrated fit from the
:mod:`~horovod_tpu.plan.calibrate` microbenchmark sweep — and
:func:`price_plan` / :func:`price_step` turn a validated
:class:`~horovod_tpu.plan.ir.WirePlan` / :class:`~horovod_tpu.plan.
planner.StepPlan` into predicted milliseconds:

* **bytes term** — per-leg wire bytes (the exact
  :func:`~horovod_tpu.plan.planner.predict_leg_bytes` formulas the
  trace-time accounting charges) divided by the link bandwidth;
* **alpha term** — per-leg launch latency: a ring collective over ``k``
  ranks serializes ``k-1`` hops, each paying the link's latency, once
  per fused bucket (so the fusion threshold is priced: more buckets =
  more alphas) amortized over the overlap flight width;
* **quant term** — blockwise int8 quantize + dequant-accumulate kernel
  time on the fp-equivalent payload of every int8 leg at the link's
  ``quant_rate_gbps``;
* **overlap credit** — an overlap-scheduled plan hides its streamed wire
  under backward compute except the final flight's tail
  (``1/buckets`` of the wire, the PR-5 streaming machinery's exposed
  remainder), capped by the available ``compute_ms`` when the caller
  knows it.

The ``modeled_ms`` field of every priced leg is the PURE bytes/bandwidth
number at the static ``HOROVOD_BENCH_*_GBPS`` knobs — exactly what the
trace-time :class:`~horovod_tpu.plan.accounting.WireStats` model would
charge — so ``predicted - modeled`` is the drift surface
``scripts/cost_smoke.sh`` and ``tests/test_cost.py`` check
(docs/cost-model.md).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, List, Optional, Tuple

from . import ir
from .accounting import bench_gbps

# Static launch-latency defaults (microseconds per ring hop). ICI links
# are on-die/board traces; DCN and pod hops cross host NICs. Override
# with HOROVOD_BENCH_{ICI,DCN,POD}_LAT_US (pod defaults to the DCN
# value, like the bandwidth knob).
DEFAULT_ICI_LAT_US = 1.0
DEFAULT_DCN_LAT_US = 25.0

# Static blockwise int8 quantize+dequant processing rate (GB/s of
# fp-equivalent payload through the kernel pair). Override with
# HOROVOD_BENCH_QUANT_GBPS; the calibration sweep measures it.
DEFAULT_QUANT_GBPS = 50.0

HOPS = ("ici", "dcn", "pod")


@dataclasses.dataclass(frozen=True)
class LinkClass:
    """One link class of the machine hierarchy, as the cost model sees
    it: sustained ``bandwidth_gbps`` (GB/s per device), per-hop launch
    ``latency_us`` (the alpha of the alpha-beta model), and
    ``quant_rate_gbps`` — the rate the blockwise int8 quantize +
    dequant-accumulate kernel pair processes fp-equivalent payload
    destined for this link."""

    bandwidth_gbps: float
    latency_us: float
    quant_rate_gbps: float

    def as_dict(self) -> dict:
        return {"bandwidth_gbps": float(self.bandwidth_gbps),
                "latency_us": float(self.latency_us),
                "quant_rate_gbps": float(self.quant_rate_gbps)}

    @classmethod
    def from_dict(cls, d: dict) -> "LinkClass":
        return cls(bandwidth_gbps=float(d["bandwidth_gbps"]),
                   latency_us=float(d["latency_us"]),
                   quant_rate_gbps=float(d["quant_rate_gbps"]))


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Per-link-class parameters pricing every plan.

    ``source`` records provenance: ``"static"`` (the env-default
    triples) or ``"calibrated"`` (a :mod:`~horovod_tpu.plan.calibrate`
    sweep, in which case ``geometry`` names the mesh fingerprint the
    sweep ran on)."""

    ici: LinkClass
    dcn: LinkClass
    pod: LinkClass
    source: str = "static"
    geometry: Optional[str] = None

    def link(self, hop: str) -> LinkClass:
        if hop not in HOPS:
            raise ValueError(f"unknown link class {hop!r}: one of {HOPS}")
        return getattr(self, hop)

    def as_dict(self) -> dict:
        return {"ici": self.ici.as_dict(), "dcn": self.dcn.as_dict(),
                "pod": self.pod.as_dict(), "source": self.source,
                "geometry": self.geometry}

    @classmethod
    def from_env(cls) -> "CostModel":
        """The static model: bandwidths from the HOROVOD_BENCH_*_GBPS
        knobs (the same numbers behind every modeled-time report),
        latencies/quant rates from their env knobs or defaults."""
        ici_bw, dcn_bw, pod_bw = bench_gbps()
        ici_lat = float(os.environ.get("HOROVOD_BENCH_ICI_LAT_US",
                                       str(DEFAULT_ICI_LAT_US)))
        dcn_lat = float(os.environ.get("HOROVOD_BENCH_DCN_LAT_US",
                                       str(DEFAULT_DCN_LAT_US)))
        pod_lat = float(os.environ.get("HOROVOD_BENCH_POD_LAT_US",
                                       str(dcn_lat)))
        quant = float(os.environ.get("HOROVOD_BENCH_QUANT_GBPS",
                                     str(DEFAULT_QUANT_GBPS)))
        return cls(ici=LinkClass(ici_bw, ici_lat, quant),
                   dcn=LinkClass(dcn_bw, dcn_lat, quant),
                   pod=LinkClass(pod_bw, pod_lat, quant),
                   source="static")


@dataclasses.dataclass(frozen=True)
class LegCost:
    """Predicted cost of one leg for one full (unbucketed) payload.

    ``modeled_ms`` is the bytes/bandwidth number at the STATIC modeled
    bandwidths (the WireStats trace-time model); ``wire_ms`` the same
    bytes at the cost model's (possibly calibrated) bandwidth;
    ``alpha_ms`` the per-bucket launch latency of the leg's ring;
    ``quant_ms`` the int8 quantize/dequant kernel time. ``total_ms`` is
    wire + alpha + quant for a single-bucket issue."""

    leg: ir.Leg
    hop: str
    bytes: float
    modeled_ms: float
    wire_ms: float
    alpha_ms: float
    quant_ms: float

    @property
    def total_ms(self) -> float:
        return self.wire_ms + self.alpha_ms + self.quant_ms


@dataclasses.dataclass(frozen=True)
class PlanCost:
    """Aggregated cost of one :class:`~horovod_tpu.plan.ir.WirePlan`."""

    plan: ir.WirePlan
    legs: Tuple[LegCost, ...]

    def _sum(self, field: str) -> float:
        return sum(getattr(l, field) for l in self.legs)

    @property
    def wire_ms(self) -> float:
        return self._sum("wire_ms")

    @property
    def modeled_ms(self) -> float:
        return self._sum("modeled_ms")

    @property
    def alpha_ms(self) -> float:
        return self._sum("alpha_ms")

    @property
    def quant_ms(self) -> float:
        return self._sum("quant_ms")

    @property
    def total_ms(self) -> float:
        return self._sum("total_ms")

    def by_leg(self, leg: ir.Leg) -> Tuple[float, float]:
        """(modeled_ms, predicted_ms) summed over the rows charged to
        ``leg`` — the plan table's two columns."""
        modeled = sum(l.modeled_ms for l in self.legs if l.leg is leg)
        pred = sum(l.total_ms for l in self.legs if l.leg is leg)
        return modeled, pred


@dataclasses.dataclass(frozen=True)
class StepCost:
    """Predicted per-step wire cost of a whole
    :class:`~horovod_tpu.plan.planner.StepPlan` (gradient plan + ZeRO
    gather plan when present), bucketed at the plan's fusion threshold.

    ``predicted_ms`` is the headline number (sync cost minus the overlap
    hiding credit); ``wire_ms``/``alpha_ms``/``quant_ms`` its additive
    terms; ``modeled_ms`` the pure bytes-at-modeled-bandwidth figure the
    drift gate compares against (identical formulas to the trace-time
    WireStats accounting)."""

    plan_costs: Tuple[PlanCost, ...]
    buckets: int
    flights: int
    wire_ms: float
    modeled_ms: float
    alpha_ms: float
    quant_ms: float
    hidden_ms: float
    source: str
    # Pipeline terms (docs/pipeline.md): the inter-stage send wire over
    # the whole schedule (2 x ticks issues) plus the bubble cost when
    # compute_ms is known — zero with pp off.
    pp_ms: float = 0.0
    pp_bubble_ms: float = 0.0
    # T3 bubble-fill credit (docs/pipeline.md): streamed ZeRO wire the
    # schedule's idle ticks absorb — the bubble is busy moving bytes
    # instead of idling, so the step does not pay both. Bounded by
    # pp_bubble_ms at construction; zero without pp + ZeRO-3 + overlap.
    pp_fill_ms: float = 0.0
    # MoE term (docs/moe.md): the expert dispatch/combine a2a wire (2
    # issues per MoE layer of a capacity-factor-scaled buffer) — zero
    # with MoE off.
    moe_ms: float = 0.0

    @property
    def sync_ms(self) -> float:
        return self.wire_ms + self.alpha_ms + self.quant_ms

    @property
    def predicted_ms(self) -> float:
        return (self.sync_ms - self.hidden_ms + self.pp_ms
                + self.pp_bubble_ms - self.pp_fill_ms + self.moe_ms)

    def as_dict(self) -> dict:
        return {
            "predicted_ms": round(self.predicted_ms, 6),
            "wire_ms": round(self.wire_ms, 6),
            "modeled_ms": round(self.modeled_ms, 6),
            "alpha_ms": round(self.alpha_ms, 6),
            "quant_ms": round(self.quant_ms, 6),
            "hidden_ms": round(self.hidden_ms, 6),
            "pp_ms": round(self.pp_ms, 6),
            "pp_bubble_ms": round(self.pp_bubble_ms, 6),
            "pp_fill_ms": round(self.pp_fill_ms, 6),
            "moe_ms": round(self.moe_ms, 6),
            "buckets": self.buckets,
            "model": self.source,
        }


def _ring_size(hop: str, mesh_sizes: Tuple[int, int, int]) -> int:
    nl, nc, npod = mesh_sizes
    return {ir.LEVEL_HOP[ir.ICI]: nl, ir.LEVEL_HOP[ir.DCN]: nc,
            ir.LEVEL_HOP[ir.POD]: npod}.get(hop, 1)


def price_plan(plan: ir.WirePlan, n: int, itemsize: float, mesh_shape,
               model: Optional[CostModel] = None, *,
               buckets: int = 1, ep: int = 0) -> PlanCost:
    """Price one plan for a payload of ``n`` elements: per-leg bytes
    from the exact trace-time formulas, alpha per ring hop per bucket,
    quant kernel time on the int8 legs' fp-equivalent payload. ``ep``
    is the hvd_ep exchange width of an ``a2a`` plan (docs/moe.md)."""
    from . import planner as _planner  # call-time: planner imports cost

    model = model or CostModel.from_env()
    static = CostModel.from_env()
    nl, nc, npod = _planner._mesh_sizes(mesh_shape)
    rows = _planner.predict_leg_bytes(plan, n, itemsize, mesh_shape,
                                      ep=ep)
    legs: List[LegCost] = []
    for r in rows:
        hop, b = r["hop"], float(r["bytes"])
        if hop not in HOPS:
            legs.append(LegCost(r["leg"], hop, b, 0.0, 0.0, 0.0, 0.0))
            continue
        lk = model.link(hop)
        k = _ring_size(hop, (nl, nc, npod))
        wire_ms = b / (lk.bandwidth_gbps * 1e9) * 1e3
        modeled_ms = b / (static.link(hop).bandwidth_gbps * 1e9) * 1e3
        if r["leg"].primitive in (ir.SEND, ir.ALL_TO_ALL):
            # A send leg is ONE point-to-point hop, and a tiled
            # all_to_all lowers to ONE fused exchange — exactly one
            # launch latency per issue (docs/pipeline.md, docs/moe.md).
            alpha_ms = lk.latency_us * buckets / 1e3
        else:
            alpha_ms = lk.latency_us * max(0, k - 1) * buckets / 1e3
        quant_ms = 0.0
        if r["leg"].wire_dtype == ir.INT8:
            # Quantize + dequant-accumulate on the fp-equivalent payload
            # of this hop.
            rate = lk.quant_rate_gbps * 1e9
            quant_ms = float(r["fp_bytes"]) / rate * 1e3
        legs.append(LegCost(r["leg"], hop, b, modeled_ms, wire_ms,
                            alpha_ms, quant_ms))
    return PlanCost(plan, tuple(legs))


def price_step(step_plan, payload_bytes: float, *,
               itemsize: float = 4.0, mesh_shape=None,
               model: Optional[CostModel] = None,
               compute_ms: Optional[float] = None) -> StepCost:
    """Price a resolved :class:`~horovod_tpu.plan.planner.StepPlan` for
    a gradient payload of ``payload_bytes``.

    The fusion threshold buckets the payload (``ceil(payload /
    threshold)`` collectives per plan); each bucket pays every leg's
    alpha, amortized over the overlap flight width
    (``num_comm_streams`` buckets issue per flight). With ``overlap``
    on, the streamed wire hides under backward compute except the last
    flight's tail — ``compute_ms`` caps the credit when known (pass
    ``None`` to assume ample compute, the shortlist-ranking default)."""
    model = model or CostModel.from_env()
    mesh_shape = mesh_shape if mesh_shape is not None \
        else step_plan.mesh_shape
    n = max(1, int(payload_bytes / max(1e-9, itemsize)))
    thr = max(1, int(step_plan.fusion_threshold_bytes))
    buckets = max(1, int(math.ceil(payload_bytes / thr)))
    streams = max(1, int(step_plan.num_comm_streams)) \
        if step_plan.overlap else 1
    flights = int(math.ceil(buckets / streams))
    plan_costs = tuple(
        price_plan(p, n, itemsize, mesh_shape, model, buckets=1)
        for p in step_plan.plans)
    wire_ms = sum(pc.wire_ms for pc in plan_costs)
    modeled_ms = sum(pc.modeled_ms for pc in plan_costs)
    quant_ms = sum(pc.quant_ms for pc in plan_costs)
    # Alpha: every leg's ring latency once per FLIGHT (buckets in the
    # same flight launch together; their latencies overlap).
    alpha_ms = sum(pc.alpha_ms for pc in plan_costs) * flights
    hidden_ms = 0.0
    if step_plan.overlap and buckets > 1:
        hideable = wire_ms * (1.0 - 1.0 / buckets)
        hidden_ms = (hideable if compute_ms is None
                     else max(0.0, min(hideable, float(compute_ms))))
    moe_ms = 0.0
    moe = getattr(step_plan, "moe", None)
    experts = int(getattr(step_plan, "moe_experts", 0) or 0)
    if moe is not None and experts > 1:
        # MoE pricing (docs/moe.md): one MoE layer issues two a2a
        # exchanges per step (dispatch + combine) of a dispatch buffer
        # sized capacity_factor x the activation payload — approximated
        # against the caller's payload when no activation size is
        # known, which preserves the ranking the shortlist needs: a
        # bigger capacity factor moves proportionally more bytes, the
        # int8 wire moves ~4x fewer at quantize-kernel cost.
        cap = float(getattr(step_plan, "moe_capacity_factor", 0.0)
                    or 1.0)
        buf_n = max(1, int(n * max(0.25, cap)))
        mpc = price_plan(moe, buf_n, itemsize, mesh_shape, model,
                         ep=experts)
        moe_ms = mpc.total_ms * 2
    pp_ms = 0.0
    pp_bubble_ms = 0.0
    pp_fill_ms = 0.0
    send = getattr(step_plan, "send", None)
    stages = int(getattr(step_plan, "pp_stages", 0) or 0)
    if send is not None and stages > 1:
        # Pipeline pricing (docs/pipeline.md): the schedule issues
        # ~2*(M*v + S - 1) send hops per step (one activation + one
        # grad hop per tick) of a per-microbatch activation payload —
        # approximated as payload/M when the caller has no activation
        # size to give — and the interleaved bubble idles
        # (S-1)/(M*v + S - 1) of the step when compute_ms is known.
        M = max(1, int(step_plan.pp_microbatches or 2 * stages))
        v = max(1, int(getattr(step_plan, "pp_interleave", 1) or 1))
        act_n = max(1, n // M)
        spc = price_plan(send, act_n, itemsize, mesh_shape, model)
        ticks = 2 * M * v + 2 * (stages - 1)
        pp_ms = spc.total_ms * ticks
        if compute_ms is not None:
            sched_name = str(getattr(step_plan, "pp_schedule", "") or "")
            if sched_name == "zb1":
                # Zero-bubble: the analytic interleaved bound no longer
                # applies — price the EXACT measured bubble of the zb
                # tables (the same builder the step executes).
                from ..parallel import pipeline as _pipeline  # lazy: cycle

                try:
                    bf = _pipeline.build_interleaved_schedule(
                        M, stages, v, family="zb1").bubble_fraction
                except ValueError:
                    # un-buildable geometry (e.g. M % S with v > 1):
                    # fall back to the analytic interleaved bound
                    bf = (stages - 1) / (M * v + stages - 1)
            else:
                bf = (stages - 1) / (M * v + stages - 1)
            pp_bubble_ms = float(compute_ms) * bf / max(1e-9, 1.0 - bf)
            # T3 fill credit (docs/pipeline.md): with ZeRO-3 + overlap
            # the forward-order bucket gathers issue into the bubble's
            # idle ticks, so the streamed wire NOT already hidden under
            # backward compute is absorbed by the bubble instead —
            # capped at the bubble itself (it cannot hide more wire
            # than it has idle time).
            if (int(getattr(step_plan, "zero_stage", 0) or 0) >= 3
                    and step_plan.overlap
                    and getattr(step_plan, "gather", None) is not None):
                pp_fill_ms = min(pp_bubble_ms,
                                 max(0.0, wire_ms - hidden_ms))
    return StepCost(plan_costs=plan_costs, buckets=buckets,
                    flights=flights, wire_ms=wire_ms,
                    modeled_ms=modeled_ms, alpha_ms=alpha_ms,
                    quant_ms=quant_ms, hidden_ms=hidden_ms,
                    source=model.source, pp_ms=pp_ms,
                    pp_bubble_ms=pp_bubble_ms, pp_fill_ms=pp_fill_ms,
                    moe_ms=moe_ms)


def price_a2a(plan: ir.WirePlan, payload_bytes: float, *,
              ep: int, issues: int = 1, itemsize: float = 4.0,
              mesh_shape=(1, 1),
              model: Optional[CostModel] = None) -> dict:
    """Price ``issues`` identical a2a exchanges of a ``payload_bytes``
    dispatch buffer over ``ep`` expert groups: the per-exchange
    wire/alpha/quant terms times the layer's issue count (two per MoE
    layer — dispatch, then combine) — the predicted side of the a2a
    drift pair (docs/moe.md; the bytes are held to the traced ones by
    ``tests/test_moe.py``). ``modeled_ms`` is the
    same bytes at the static modeled bandwidths, exactly what the
    trace-time accounting would charge for the same issues."""
    model = model or CostModel.from_env()
    n = max(1, int(payload_bytes / max(1e-9, itemsize)))
    pc = price_plan(plan, n, itemsize, mesh_shape, model, ep=ep)
    return {
        "predicted_ms": pc.total_ms * issues,
        "modeled_ms": pc.modeled_ms * issues,
        "wire_bytes": sum(l.bytes for l in pc.legs) * issues,
        "model": model.source,
    }


def price_send(plan: ir.WirePlan, payload_bytes: float, *,
               issues: int = 1, itemsize: float = 4.0,
               mesh_shape=(1, 1),
               model: Optional[CostModel] = None) -> dict:
    """Price ``issues`` identical send-plan hops of a ``payload_bytes``
    activation: the per-send wire/alpha/quant terms times the schedule's
    issue count — the predicted side of the send-leg drift pair
    (docs/pipeline.md; ``tests/test_pp.py`` holds the bytes to the
    traced ones). ``modeled_ms`` is the same
    bytes at the static modeled bandwidths, exactly what the trace-time
    accounting would charge for the same issues."""
    model = model or CostModel.from_env()
    n = max(1, int(payload_bytes / max(1e-9, itemsize)))
    pc = price_plan(plan, n, itemsize, mesh_shape, model)
    return {
        "predicted_ms": pc.total_ms * issues,
        "modeled_ms": pc.modeled_ms * issues,
        "wire_bytes": sum(l.bytes for l in pc.legs) * issues,
        "model": model.source,
    }


def price_kv_migrate(plan: ir.WirePlan, payload_bytes: float, *,
                     transfers: int = 1, itemsize: float = 4.0,
                     mesh_shape=(1, 1),
                     model: Optional[CostModel] = None) -> dict:
    """Price ``transfers`` prefill→decode KV handoffs of a
    ``payload_bytes`` slot payload each: the per-migration
    wire/alpha/quant terms times the handoff count — the predicted side
    of the migration drift pair (docs/serving.md;
    ``tests/test_serve_disagg.py`` holds the bytes to the lowering's). ``modeled_ms`` is the same bytes at the static
    modeled bandwidths, exactly what :func:`~horovod_tpu.plan.compiler.
    lower_kv_migrate` charges for the same transfers (residual pass
    included — the leg-byte predictor doubles quantized bytes when the
    plan carries the error-feedback residual slot)."""
    model = model or CostModel.from_env()
    n = max(1, int(payload_bytes / max(1e-9, itemsize)))
    pc = price_plan(plan, n, itemsize, mesh_shape, model)
    return {
        "predicted_ms": pc.total_ms * transfers,
        "modeled_ms": pc.modeled_ms * transfers,
        "wire_bytes": sum(l.bytes for l in pc.legs) * transfers,
        "model": model.source,
    }


def predict_hop_ms(hop: str, nbytes: float,
                   model: Optional[CostModel] = None) -> float:
    """Predicted transfer milliseconds of ``nbytes`` on one link class
    under the resolved (calibrated-else-static) cost model: the
    bytes/bandwidth term plus one launch latency. This is the
    *predicted* side of the monitor layer's link-health score
    (``monitor/straggler.observe_wire``, docs/observability.md): a hop
    whose measured wire-ms persistently exceeds this prediction is
    either degraded or the calibration is stale."""
    model = model or resolve()
    lk = model.link(hop)
    return (float(nbytes) / (lk.bandwidth_gbps * 1e9) * 1e3
            + lk.latency_us / 1e3)


def resolve(mesh_shape=None) -> CostModel:
    """The cost model for ``mesh_shape``: the calibrated triples when a
    matching-geometry sweep is on disk (docs/cost-model.md), else the
    static env defaults. Never raises — pricing must never abort
    training."""
    from . import calibrate as _calibrate

    return _calibrate.get_cost_model(mesh_shape=mesh_shape)
