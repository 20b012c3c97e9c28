"""The planner: derive a wire plan from today's knob set.

Every knob combination the collective stack used to hand-compose —
``quantized`` × ``zero_stage`` × ``overlap`` × ``hierarchical`` × stream
count — is one point in plan space:

==============================  =======================================
knobs                            gradient wire plan
==============================  =======================================
(defaults)                       ``allreduce: flat.psum`` (XLA
                                 decomposes over ICI/DCN itself)
``hierarchical=True``            ``allreduce: ici.rs > dcn.psum >
                                 ici.ag`` (+ ``pod.psum`` on a 3-level
                                 mesh)
``quantized=True``               ``allreduce: ici.rs > dcn.rs[int8] >
                                 dcn.ag[int8] > ici.ag``
``zero_stage>0``                 split in half around the optimizer
                                 update: a ``reduce_scatter`` plan for
                                 the gradients + an ``all_gather`` plan
                                 for the updates (stage 3 moves the
                                 gather to the next forward)
``overlap`` / ``streams``        placement attributes on any of the
                                 above (reverse-layer issue order,
                                 flight width) — never the math
==============================  =======================================

:func:`describe_plan` is the debug API (``hvd.describe_plan(**knobs)``):
it resolves unset knobs exactly like ``DistributedOptimizer`` would (env
config included) and returns a :class:`StepPlan` whose :meth:`~StepPlan.
table` renders legs, hops, wire dtypes, streams, and predicted per-device
wire bytes from the trace-time cost model — ``print(hvd.describe_plan(
...).table())`` shows it, and golden-text tests (``tests/test_plan.py``)
pin it so plan regressions show up as readable diffs.

:func:`encode_tuned` / :func:`decode_tuned` are the autotuner's compact
plan encoding (leg order, per-hop dtype, stream placement): the GP
searches this space instead of three disconnected relaxed-categorical
booleans, and configurations that compile to the SAME wire (e.g.
``hierarchical`` under ZeRO, where the rs/ag split ignores it) collapse
to one plan — one trial, not two recompiles.
"""

from __future__ import annotations

import dataclasses
import re
from typing import List, Optional, Tuple

from ..common import basics
from ..common.config import _env_bool, _env_int
from .ir import (ALL_GATHER, ALL_TO_ALL, DCN, FLAT, ICI, INT8, PAYLOAD,
                 POD, PSUM, REDUCE_SCATTER, SEND, Leg, PlanError,
                 WirePlan)

_AXIS_LEVEL = {basics.LOCAL_AXIS: ICI, basics.CROSS_AXIS: DCN,
               basics.POD_AXIS: POD}


def _resolve_quantized_pod(quantized_pod: Optional[bool]) -> bool:
    """Per-call arg > Config > HOROVOD_QUANTIZED_POD env — whether the
    3-level tree plan's pod hop rides the blockwise-int8 rs+ag pair
    instead of the exact psum."""
    if quantized_pod is not None:
        return bool(quantized_pod)
    cfg = basics.config() if basics.is_initialized() else None
    return (cfg.quantized_pod if cfg is not None
            else _env_bool("HOROVOD_QUANTIZED_POD", False))


def levels_of(axes_t) -> Optional[Tuple[str, ...]]:
    """Map a bound axis tuple onto plan levels, or None when the tuple
    names non-Horovod axes (custom ``axes=`` — always lowered flat)."""
    try:
        return tuple(_AXIS_LEVEL[a] for a in axes_t)
    except KeyError:
        return None


# ---------------------------------------------------------------------------
# Canonical plan constructors.
# ---------------------------------------------------------------------------


def flat_plan(collective: str, *, streams: int = 1,
              overlap: bool = False) -> WirePlan:
    prim = {"allreduce": PSUM, "reduce_scatter": REDUCE_SCATTER,
            "all_gather": ALL_GATHER}[collective]
    return WirePlan(collective, (Leg(FLAT, prim),), streams=streams,
                    overlap=overlap).validate()


def tree_allreduce_plan(*, pod: bool = False, streams: int = 1,
                        overlap: bool = False,
                        quantized_pod: bool = False,
                        block: Optional[int] = None) -> WirePlan:
    legs = [Leg(ICI, REDUCE_SCATTER), Leg(DCN, PSUM)]
    if pod and quantized_pod:
        # The quantized pod hop (docs/wire-plan.md): the pod level as
        # the int8 rs+ag pair — the EQuARX decomposition on the slowest
        # link of a 3-level mesh — instead of the exact psum.
        legs.append(Leg(POD, REDUCE_SCATTER, INT8, block=block))
        legs.append(Leg(POD, ALL_GATHER, INT8, block=block))
    elif pod:
        legs.append(Leg(POD, PSUM))
    legs.append(Leg(ICI, ALL_GATHER))
    return WirePlan("allreduce", tuple(legs), streams=streams,
                    overlap=overlap).validate()


def quantized_allreduce_plan(*, block: Optional[int] = None,
                             error_feedback: bool = False,
                             streams: int = 1,
                             overlap: bool = False) -> WirePlan:
    legs = (
        Leg(ICI, REDUCE_SCATTER),
        Leg(DCN, REDUCE_SCATTER, INT8, block=block,
            error_feedback=error_feedback),
        Leg(DCN, ALL_GATHER, INT8, block=block,
            error_feedback=error_feedback),
        Leg(ICI, ALL_GATHER),
    )
    return WirePlan("allreduce", legs, streams=streams,
                    overlap=overlap).validate()


def zero_reduce_scatter_plan(*, quantized: bool = False,
                             block: Optional[int] = None,
                             error_feedback: bool = False,
                             streams: int = 1,
                             overlap: bool = False) -> WirePlan:
    """The ZeRO gradient wire (the reduce half of the quantized
    allreduce, stopped before the optimizer update)."""
    dcn = (Leg(DCN, REDUCE_SCATTER, INT8, block=block,
               error_feedback=error_feedback) if quantized
           else Leg(DCN, REDUCE_SCATTER, PAYLOAD,
                    error_feedback=error_feedback))
    return WirePlan("reduce_scatter",
                    (Leg(ICI, REDUCE_SCATTER), dcn),
                    streams=streams, overlap=overlap).validate()


def zero_all_gather_plan(*, quantized: bool = False,
                         block: Optional[int] = None,
                         error_feedback: bool = False,
                         streams: int = 1,
                         overlap: bool = False) -> WirePlan:
    """The ZeRO update broadcast (the gather half)."""
    if quantized:
        legs = (Leg(DCN, ALL_GATHER, INT8, block=block,
                    error_feedback=error_feedback),
                Leg(ICI, ALL_GATHER))
        return WirePlan("all_gather", legs, streams=streams,
                        overlap=overlap).validate()
    return flat_plan("all_gather", streams=streams, overlap=overlap)


def send_plan(level: str = DCN, *, quantized: bool = False,
              block: Optional[int] = None,
              error_feedback: bool = False) -> WirePlan:
    """The pipeline's inter-stage activation wire (docs/pipeline.md): a
    single point-to-point ``send`` leg on the link class the hvd_pp hop
    crosses. ``quantized`` rides it blockwise-int8 with error feedback —
    legal on the DCN/pod hops only (the EQuARX placement rule; an ICI
    send always rides the payload dtype)."""
    if quantized:
        leg = Leg(level, SEND, INT8, block=block,
                  error_feedback=error_feedback)
    else:
        leg = Leg(level, SEND, PAYLOAD)
    return WirePlan("send", (leg,)).validate()


def pp_send_level(mesh_shape) -> str:
    """The link class an hvd_pp hop crosses: the pp axis leads the mesh
    (consecutive stages sit a whole data-mesh apart in device order), so
    the hop rides the SLOWEST link class present — pod on a multi-pod
    mesh, dcn across hosts, ici on a single host."""
    nl, nc, npod = _mesh_sizes(mesh_shape)
    if npod > 1:
        return POD
    return DCN if nc > 1 else ICI


def derive_send(*, mesh_shape, quantized: bool = False,
                block: Optional[int] = None,
                error_feedback: Optional[bool] = None) -> WirePlan:
    """Derive the pipeline send plan for a mesh: the level comes from
    :func:`pp_send_level`; ``quantized`` is forced off on an ICI hop
    (int8 is illegal there — compression belongs on slow links)."""
    level = pp_send_level(mesh_shape)
    q = bool(quantized) and level in (DCN, POD)
    ef = q if error_feedback is None else (error_feedback and q)
    return send_plan(level, quantized=q, block=block, error_feedback=ef)


def kv_migrate_plan(level: str = DCN, *, quantized: bool = False,
                    block: Optional[int] = None,
                    error_feedback: bool = False) -> WirePlan:
    """The disaggregated-serving KV handoff wire (docs/serving.md): a
    single point-to-point ``send`` leg carrying one finished prefill's
    KV pages from a prefill replica to its decode replica. ``quantized``
    rides it blockwise-int8 (DCN/pod hops only, the EQuARX placement
    rule). ``error_feedback`` on a migration leg means the RESIDUAL
    pass: a one-shot transfer has no next step to feed the error into,
    so the compiler ships a second int8 pass over the first pass's
    quantization error on the same wire — 2x the quantized bytes,
    error collapsed to ~(absmax/127)^2, argmax-safe for decode."""
    if quantized:
        leg = Leg(level, SEND, INT8, block=block,
                  error_feedback=error_feedback)
    else:
        leg = Leg(level, SEND, PAYLOAD)
    return WirePlan("kv_migrate", (leg,)).validate()


def kv_migrate_level(mesh_shape) -> str:
    """The link class a prefill→decode handoff crosses: replica groups
    partition the device list contiguously (docs/serving.md), so the
    hop between two replicas rides the SLOWEST link class present —
    the same geometry argument as the pipeline/expert hops."""
    return pp_send_level(mesh_shape)


def derive_kv_migrate(*, mesh_shape, quantized: bool = False,
                      block: Optional[int] = None,
                      error_feedback: Optional[bool] = None) -> WirePlan:
    """Derive the KV migration plan for a mesh: the level comes from
    :func:`kv_migrate_level`; ``quantized`` is forced off on an ICI hop
    (int8 is illegal there), and a quantized migration defaults to the
    residual (error-feedback) pass so the handoff stays argmax-safe."""
    level = kv_migrate_level(mesh_shape)
    q = bool(quantized) and level in (DCN, POD)
    ef = q if error_feedback is None else (error_feedback and q)
    return kv_migrate_plan(level, quantized=q, block=block,
                           error_feedback=ef)


def predict_kv_migrate_bytes(plan: WirePlan, n: int,
                             itemsize: float) -> List[dict]:
    """Per-leg predicted wire bytes of ONE migration of an ``n``-element
    KV payload — the same formula :func:`~horovod_tpu.plan.compiler.
    lower_kv_migrate` charges at transfer time (the residual pass rides
    the same wire again), so predicted == accounted by construction.
    Row schema matches :func:`predict_leg_bytes`."""
    (leg,) = plan.legs
    hop = {ICI: "ici", DCN: "dcn", POD: "pod"}[leg.level]
    fp = float(n) * itemsize
    if leg.wire_dtype == INT8:
        from .compiler import quant_wire_bytes

        wire = quant_wire_bytes(n, leg.block or 256)
        if leg.error_feedback:
            wire *= 2.0
    else:
        wire = fp
    return [{"leg": leg, "hop": hop, "bytes": wire, "fp_bytes": fp}]


def a2a_plan(level: str = DCN, *, quantized: bool = False,
             block: Optional[int] = None,
             error_feedback: bool = False) -> WirePlan:
    """The MoE dispatch/combine wire (docs/moe.md): a single tiled
    ``all_to_all`` row exchange on the link class the hvd_ep hop
    crosses. ``quantized`` rides it blockwise-int8 with optional error
    feedback — legal on the DCN/pod hops only (the EQuARX placement
    rule, exactly like the pipeline send leg)."""
    if quantized:
        leg = Leg(level, ALL_TO_ALL, INT8, block=block,
                  error_feedback=error_feedback)
    else:
        leg = Leg(level, ALL_TO_ALL, PAYLOAD)
    return WirePlan("a2a", (leg,)).validate()


def ep_a2a_level(mesh_shape) -> str:
    """The link class an hvd_ep hop crosses: identical geometry to the
    pipeline hop — the ep axis leads the mesh, so one hop jumps a whole
    data mesh and rides the SLOWEST link class present (docs/moe.md)."""
    return pp_send_level(mesh_shape)


def derive_a2a(*, mesh_shape, quantized: bool = False,
               block: Optional[int] = None,
               error_feedback: Optional[bool] = None) -> WirePlan:
    """Derive the MoE a2a plan for a data mesh: the level comes from
    :func:`ep_a2a_level`; ``quantized`` is forced off on an ICI hop
    (int8 is illegal there — compression belongs on slow links)."""
    level = ep_a2a_level(mesh_shape)
    q = bool(quantized) and level in (DCN, POD)
    ef = q if error_feedback is None else (error_feedback and q)
    return a2a_plan(level, quantized=q, block=block, error_feedback=ef)


def predict_a2a_bytes(plan: WirePlan, n: int, itemsize: float,
                      ep: int) -> List[dict]:
    """Per-leg predicted wire bytes of ONE a2a exchange of an
    ``n``-element buffer over ``ep`` expert groups — the same formula
    :func:`~horovod_tpu.plan.compiler.lower_a2a` charges at trace time
    (``ep - 1`` of the ``ep`` destination row blocks cross the wire),
    so predicted == accounted by construction. Row schema matches
    :func:`predict_leg_bytes`."""
    (leg,) = plan.legs
    hop = {ICI: "ici", DCN: "dcn", POD: "pod"}[leg.level]
    ep = max(1, int(ep))
    seg = n // ep
    fp = float(seg) * (ep - 1) * itemsize
    if leg.wire_dtype == INT8:
        from .compiler import quant_wire_bytes

        wire = quant_wire_bytes(seg, leg.block or 256) * (ep - 1)
    else:
        wire = fp
    return [{"leg": leg, "hop": hop, "bytes": wire, "fp_bytes": fp}]


def pp_bubble_bound(stages: int, microbatches: int) -> float:
    """The no-overlap GPipe analytic bubble bound ``(S-1)/(M+S-1)`` —
    the fraction ``tests/test_pp.py`` holds every interleaved and
    zero-bubble schedule strictly under (docs/pipeline.md)."""
    s, m = int(stages), max(1, int(microbatches))
    return (s - 1) / (m + s - 1) if s > 1 else 0.0


# ---------------------------------------------------------------------------
# Knob → plan derivation (what the entry points call per trace).
# ---------------------------------------------------------------------------


def derive_allreduce(*, levels, quantized: bool, hierarchical: bool,
                     block: Optional[int] = None,
                     error_feedback: bool = False,
                     streams: int = 1, overlap: bool = False,
                     quantized_pod: Optional[bool] = None) -> WirePlan:
    """Today's allreduce knob combination as a plan. ``levels`` is the
    bound-axis level tuple (None for custom axes → flat).
    ``quantized_pod`` (HOROVOD_QUANTIZED_POD) rides the 3-level tree
    plan's pod hop as the int8 rs+ag pair."""
    lvls = set(levels or ())
    if quantized and lvls == {ICI, DCN}:
        return quantized_allreduce_plan(block=block,
                                        error_feedback=error_feedback,
                                        streams=streams, overlap=overlap)
    if hierarchical and {ICI, DCN} <= lvls:
        return tree_allreduce_plan(
            pod=POD in lvls, streams=streams, overlap=overlap,
            quantized_pod=(POD in lvls
                           and _resolve_quantized_pod(quantized_pod)),
            block=block)
    return flat_plan("allreduce", streams=streams, overlap=overlap)


def derive_reduce_scatter(*, levels, quantized: bool,
                          error_feedback: bool = False,
                          block: Optional[int] = None,
                          streams: int = 1,
                          overlap: bool = False) -> WirePlan:
    lvls = set(levels or ())
    if lvls == {ICI, DCN} and (quantized or error_feedback):
        return zero_reduce_scatter_plan(
            quantized=quantized, block=block,
            error_feedback=error_feedback, streams=streams,
            overlap=overlap)
    return flat_plan("reduce_scatter", streams=streams, overlap=overlap)


def derive_all_gather(*, levels, quantized: bool,
                      error_feedback: bool = False,
                      block: Optional[int] = None,
                      streams: int = 1,
                      overlap: bool = False) -> WirePlan:
    lvls = set(levels or ())
    if quantized and lvls == {ICI, DCN}:
        return zero_all_gather_plan(
            quantized=True, block=block, error_feedback=error_feedback,
            streams=streams, overlap=overlap)
    return flat_plan("all_gather", streams=streams, overlap=overlap)


# ---------------------------------------------------------------------------
# Cost model: predicted per-device wire bytes per leg (the same formulas
# the compiler's trace-time accounting charges — docs/wire-plan.md).
# ---------------------------------------------------------------------------


def _mesh_sizes(mesh_shape) -> Tuple[int, int, int]:
    """(local, cross, pod) sizes of a (cross, local[, pods]) shape."""
    if len(mesh_shape) == 3:
        nc, nl, npod = mesh_shape
    else:
        (nc, nl), npod = mesh_shape, 1
    return int(nl), int(nc), int(npod)


def _quant_unit(seg: int, blk: int) -> float:
    pad_seg = (-seg) % blk + seg
    return pad_seg + (pad_seg // blk) * 4.0


def predict_leg_bytes(plan: WirePlan, n: int, itemsize: int,
                      mesh_shape, *, ep: int = 0) -> List[dict]:
    """Per-leg predicted wire bytes for a payload of ``n`` elements.
    Each row: ``{leg, hop, bytes, fp_bytes}`` where ``hop`` is the link
    class charged (``ici``/``dcn``/``pod``/``-``) and ``fp_bytes`` the
    same traffic at the payload dtype (differs only on int8 legs).
    ``ep`` is the expert-group exchange width of an ``a2a`` plan (the
    hvd_ep axis size — not derivable from the data ``mesh_shape``);
    a2a rows are zero without it."""
    if plan.collective == "a2a":
        return predict_a2a_bytes(plan, n, itemsize, ep)
    if plan.collective == "kv_migrate":
        return predict_kv_migrate_bytes(plan, n, itemsize)
    nl, nc, npod = _mesh_sizes(mesh_shape)
    world = nl * nc * npod
    isz = itemsize
    blk = plan.quant_block or 256
    sn = n // nl if nl else n
    seg_w = n // world if world else n
    rows: List[dict] = []

    def row(leg, hop, b, fp=None):
        rows.append({"leg": leg, "hop": hop, "bytes": b,
                     "fp_bytes": b if fp is None else fp})

    if plan.collective == "send":
        # One cyclic ppermute issue of the full [n] payload: every rank
        # sends its activation once (the interleaved schedule's ring);
        # same formula compiler.lower_send charges per issue at trace
        # time, so predicted == accounted by construction.
        (leg,) = plan.legs
        hop = {ICI: "ici", DCN: "dcn", POD: "pod"}[leg.level]
        if leg.wire_dtype == INT8:
            from .compiler import quant_wire_bytes

            row(leg, hop, quant_wire_bytes(n, leg.block or blk),
                float(n) * isz)
        else:
            row(leg, hop, float(n) * isz)
        return rows

    if plan.is_flat:
        leg = plan.legs[0]
        if plan.collective == "reduce_scatter":
            b = n * (nl - 1) / nl * isz
            d = (n / nl) * (nc - 1) / nc * isz
            p = (n / nl / nc) * (npod - 1) / npod * isz
        else:  # allreduce, or all_gather of the full [n] masked buffer
            b = 2.0 * n * (nl - 1) / nl * isz
            d = 2.0 * (n / nl) * (nc - 1) / nc * isz
            p = 2.0 * (n / nl / nc) * (npod - 1) / npod * isz
        row(leg, "ici", b)
        row(leg, "dcn", d)
        if npod > 1:
            row(leg, "pod", p)
        return rows

    for leg in plan.legs:
        if leg.level == ICI and leg.primitive == REDUCE_SCATTER:
            row(leg, "ici", n * (nl - 1) / nl * isz)
        elif leg.level == ICI and leg.primitive == ALL_GATHER:
            row(leg, "ici", 2.0 * n * (nl - 1) / nl * isz)
        elif leg.level in (DCN, POD) and leg.primitive == PSUM:
            k = nc if leg.level == DCN else npod
            hop = "dcn" if leg.level == DCN else "pod"
            row(leg, hop, 2.0 * (n / nl) * (k - 1) / k * isz)
        elif leg.level == POD and leg.primitive == REDUCE_SCATTER:
            # Quantized pod hop: rs[int8] on the post-ICI shard [sn].
            segp = sn // npod if npod else sn
            q = _quant_unit(segp, leg.block or blk) * npod
            row(leg, "pod", q * (npod - 1) / max(1, npod),
                float(sn) * (npod - 1) / max(1, npod) * isz)
        elif leg.level == POD and leg.primitive == ALL_GATHER:
            segp = sn // npod if npod else sn
            q = _quant_unit(segp, leg.block or blk) * npod
            row(leg, "pod", 2.0 * q * (npod - 1) / max(1, npod),
                2.0 * float(sn) * (npod - 1) / max(1, npod) * isz)
        elif leg.level == DCN and leg.primitive == REDUCE_SCATTER:
            if leg.wire_dtype == INT8:
                seg = (seg_w if plan.collective == "reduce_scatter"
                       else sn // nc)
                q = _quant_unit(seg, leg.block or blk) * nc
                row(leg, "dcn", q * (nc - 1) / nc,
                    float(sn) * (nc - 1) / nc * isz)
            else:
                row(leg, "dcn", sn * (nc - 1) / nc * isz)
        elif leg.level == DCN and leg.primitive == ALL_GATHER:
            if leg.wire_dtype != INT8:
                row(leg, "dcn", 2.0 * sn * (nc - 1) / nc * isz)
            elif plan.collective == "all_gather":
                # each rank gathers its owned 1/world segment of the
                # full [n] payload
                q = _quant_unit(seg_w, leg.block or blk)
                row(leg, "dcn", 2.0 * q * nc * (nc - 1) / nc,
                    2.0 * float(seg_w) * nc * (nc - 1) / nc * isz)
            else:
                q = _quant_unit(sn // nc, leg.block or blk) * nc
                row(leg, "dcn", 2.0 * q * (nc - 1) / nc,
                    2.0 * float(sn) * (nc - 1) / nc * isz)
        else:  # pragma: no cover - validation rejects other shapes
            row(leg, "-", 0.0)
    return rows


# ---------------------------------------------------------------------------
# StepPlan: the resolved wire plans of one training step + the knob
# record they were derived from. ``hvd.describe_plan(**knobs)`` builds it.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StepPlan:
    """Resolved plans of a training step's gradient wire.

    ``gradient`` is the gradient collective's plan (an ``allreduce``
    plan, or the ``reduce_scatter`` half under ZeRO); ``gather`` is the
    update/parameter ``all_gather`` plan (None outside ZeRO — and under
    stage 3 it runs at the HEAD of the next forward, not the update
    tail). Thread a StepPlan into ``DistributedOptimizer(plan=...)`` /
    ``hvd.value_and_grad(plan=...)`` to replace the boolean knobs (which
    remain as aliases)."""

    mesh_shape: Tuple[int, ...]
    quantized: bool
    quant_block: int
    zero_stage: int
    overlap: bool
    hierarchical: bool
    num_comm_streams: int
    fusion_threshold_bytes: int
    gradient: WirePlan
    gather: Optional[WirePlan]
    quantized_pod: bool = False
    # Pipeline parallelism (docs/pipeline.md): the inter-stage
    # activation wire (a validated send plan; None with pp off) plus the
    # schedule knobs it compiles under. ``pp_microbatches`` is the
    # per-step microbatch count M, ``pp_interleave`` the virtual-stage
    # degree v of the interleaved-1F1B schedule.
    send: Optional[WirePlan] = None
    pp_stages: int = 0
    pp_microbatches: int = 0
    pp_schedule: str = "interleaved_1f1b"
    pp_interleave: int = 1
    # Expert parallelism (docs/moe.md): the MoE dispatch/combine wire (a
    # validated a2a plan; None with MoE off) plus the routing knobs it
    # compiles under. ``moe_experts`` is the expert-group count E (the
    # hvd_ep axis size), ``moe_topk`` the per-token expert count K,
    # ``moe_capacity_factor`` the dispatch-buffer headroom.
    moe: Optional[WirePlan] = None
    moe_experts: int = 0
    moe_topk: int = 0
    moe_capacity_factor: float = 0.0
    moe_quantized: bool = False

    def encode(self) -> str:
        parts = [self.gradient.encode()]
        if self.gather is not None:
            where = "fwd" if self.zero_stage == 3 else "tail"
            parts.append(f"{where}@{self.gather.encode()}")
        if self.send is not None:
            parts.append(
                f"pp{self.pp_stages}v{self.pp_interleave}"
                f"m{self.pp_microbatches}.{self.pp_schedule}"
                f"@{self.send.encode()}")
        if self.moe is not None:
            parts.append(
                f"ep{self.moe_experts}.k{self.moe_topk}"
                f"@{self.moe.encode()}")
        return " + ".join(parts)

    @property
    def plans(self) -> Tuple[WirePlan, ...]:
        return ((self.gradient,) if self.gather is None
                else (self.gradient, self.gather))

    def table(self, payload_bytes: int = 4 * 1024 * 1024,
              itemsize: int = 4, model=None) -> str:
        """Render the step plan as a fixed-width text table (legs, hops,
        wire dtypes, streams, predicted per-device wire bytes AND
        predicted milliseconds for a ``payload_bytes`` gradient payload)
        — the golden-test format.

        The ``model ms`` column is the pure bytes-at-modeled-bandwidth
        number (the trace-time WireStats model, HOROVOD_BENCH_*_GBPS);
        ``pred ms`` adds the cost model's launch-latency and
        quantize-kernel terms (docs/cost-model.md). ``model`` is a
        :class:`~horovod_tpu.plan.cost.CostModel` (default: the static
        env triples, so golden text stays deterministic; pass
        ``get_cost_model()`` to price with a stored calibration)."""
        from . import cost as _cost

        model = model or _cost.CostModel.from_env()
        n = payload_bytes // itemsize
        mesh = "x".join(str(v) for v in self.mesh_shape)
        lines = [
            f"wire plan  mesh={mesh}  payload={payload_bytes}B "
            f"(itemsize {itemsize})",
            f"knobs: quantized={_onoff(self.quantized)} "
            f"block={self.quant_block} zero_stage={self.zero_stage} "
            f"overlap={_onoff(self.overlap)} "
            f"hierarchical={_onoff(self.hierarchical)} "
            f"streams={self.num_comm_streams} "
            f"fusion_threshold={self.fusion_threshold_bytes} "
            f"quantized_pod={_onoff(self.quantized_pod)}",
            # An int8 leg has one lowering, so `backend` reads `xla`
            # on every row; the column stays where the golden tables
            # (tests/test_plan.py) pin it.
            f"{'collective':<16} {'leg':>3} {'level':<5} "
            f"{'primitive':<14} {'wire':<10} {'ef':<3} {'backend':<7} "
            f"{'stream':>6} {'bytes/dev':>12} {'model ms':>9} "
            f"{'pred ms':>8}",
        ]
        tot = {"ici": 0.0, "dcn": 0.0, "pod": 0.0, "fp": 0.0,
               "pod_fp": 0.0}
        for plan in self.plans:
            rows = predict_leg_bytes(plan, n, itemsize, self.mesh_shape)
            plan_cost = _cost.price_plan(plan, n, itemsize,
                                         self.mesh_shape, model)
            for r in rows:
                if r["hop"] in tot:
                    tot[r["hop"]] += r["bytes"]
                if r["hop"] == "dcn":
                    tot["fp"] += r["fp_bytes"]
                elif r["hop"] == "pod":
                    tot["pod_fp"] += r["fp_bytes"]
            for li, leg in enumerate(plan.legs, start=1):
                b = sum(r["bytes"] for r in rows if r["leg"] is leg)
                modeled_ms, pred_ms = plan_cost.by_leg(leg)
                wire = leg.wire_dtype
                if leg.wire_dtype == INT8:
                    wire = f"int8/{leg.block or self.quant_block}"
                lines.append(
                    f"{plan.collective:<16} {li:>3} {leg.level:<5} "
                    f"{leg.primitive:<14} {wire:<10} "
                    f"{'yes' if leg.error_feedback else '-':<3} "
                    f"{'xla':<7} "
                    f"{leg.stream:>6} {int(round(b)):>12} "
                    f"{modeled_ms:>9.4f} {pred_ms:>8.4f}")
        if self.send is not None:
            # The pipeline wire, priced PER SEND ISSUE (one activation
            # microbatch over one hop; the schedule issues 2 x ticks of
            # these per step — cost.price_send gives the step total).
            rows = predict_leg_bytes(self.send, n, itemsize,
                                     self.mesh_shape)
            plan_cost = _cost.price_plan(self.send, n, itemsize,
                                         self.mesh_shape, model)
            for li, leg in enumerate(self.send.legs, start=1):
                b = sum(r["bytes"] for r in rows if r["leg"] is leg)
                modeled_ms, pred_ms = plan_cost.by_leg(leg)
                wire = leg.wire_dtype
                if leg.wire_dtype == INT8:
                    wire = f"int8/{leg.block or self.quant_block}"
                lines.append(
                    f"{'send':<16} {li:>3} {leg.level:<5} "
                    f"{leg.primitive:<14} {wire:<10} "
                    f"{'yes' if leg.error_feedback else '-':<3} "
                    f"{'xla':<7} "
                    f"{leg.stream:>6} {int(round(b)):>12} "
                    f"{modeled_ms:>9.4f} {pred_ms:>8.4f}")
        if self.moe is not None:
            # The MoE wire, priced PER A2A ISSUE (one dispatch-buffer
            # exchange over the hvd_ep axis; every MoE layer issues two
            # of these per step — dispatch, then combine).
            rows = predict_leg_bytes(self.moe, n, itemsize,
                                     self.mesh_shape,
                                     ep=self.moe_experts)
            plan_cost = _cost.price_plan(self.moe, n, itemsize,
                                         self.mesh_shape, model,
                                         ep=self.moe_experts)
            for li, leg in enumerate(self.moe.legs, start=1):
                b = sum(r["bytes"] for r in rows if r["leg"] is leg)
                modeled_ms, pred_ms = plan_cost.by_leg(leg)
                wire = leg.wire_dtype
                if leg.wire_dtype == INT8:
                    wire = f"int8/{leg.block or self.quant_block}"
                lines.append(
                    f"{'a2a':<16} {li:>3} {leg.level:<5} "
                    f"{leg.primitive:<14} {wire:<10} "
                    f"{'yes' if leg.error_feedback else '-':<3} "
                    f"{'xla':<7} "
                    f"{leg.stream:>6} {int(round(b)):>12} "
                    f"{modeled_ms:>9.4f} {pred_ms:>8.4f}")
        red = (tot["fp"] / tot["dcn"]) if tot["dcn"] else None
        totline = (f"totals: ici={int(round(tot['ici']))} "
                   f"dcn={int(round(tot['dcn']))} "
                   f"pod={int(round(tot['pod']))}")
        if red is not None:
            totline += (f" dcn_fp_equiv={int(round(tot['fp']))} "
                        f"dcn_reduction={red:.2f}x")
        if tot["pod"]:
            pred = tot["pod_fp"] / tot["pod"]
            totline += (f" pod_fp_equiv={int(round(tot['pod_fp']))} "
                        f"pod_reduction={pred:.2f}x")
        lines.append(totline)
        if self.send is not None:
            bound = pp_bubble_bound(self.pp_stages, self.pp_microbatches)
            lines.append(
                f"pp: stages={self.pp_stages} "
                f"interleave={self.pp_interleave} "
                f"microbatches={self.pp_microbatches} "
                f"schedule={self.pp_schedule} "
                f"gpipe_bubble_bound={bound:.4f} "
                f"(send rows priced per issue, docs/pipeline.md)")
        if self.moe is not None:
            lines.append(
                f"moe: experts={self.moe_experts} "
                f"topk={self.moe_topk} "
                f"capacity_factor={self.moe_capacity_factor:g} "
                f"quantized={_onoff(self.moe_quantized)} "
                f"(a2a rows priced per issue — dispatch + combine = 2 "
                f"per layer, docs/moe.md)")
        sc = _cost.price_step(self, payload_bytes, itemsize=itemsize,
                              mesh_shape=self.mesh_shape, model=model)
        lines.append(
            f"predicted: {sc.predicted_ms:.4f} ms step wire = bytes "
            f"{sc.wire_ms:.4f} + latency {sc.alpha_ms:.4f} + quant "
            f"{sc.quant_ms:.4f} - hidden {sc.hidden_ms:.4f} "
            f"(modeled {sc.modeled_ms:.4f} ms, {sc.buckets} bucket"
            f"{'s' if sc.buckets != 1 else ''}) "
            f"[cost model: {model.source}]")
        lines.append(f"encoding: {self.encode()}")
        return "\n".join(lines)


def _onoff(v) -> str:
    return "on" if v else "off"


def describe_plan(
    *,
    quantized: Optional[bool] = None,
    zero_stage: Optional[int] = None,
    zero: Optional[bool] = None,
    overlap: Optional[bool] = None,
    hierarchical: Optional[bool] = None,
    num_comm_streams: Optional[int] = None,
    quant_block: Optional[int] = None,
    fusion_threshold_bytes: Optional[int] = None,
    mesh_shape: Optional[Tuple[int, ...]] = None,
    error_feedback: Optional[bool] = None,
    tuned_params=None,
    quantized_pod: Optional[bool] = None,
    pp_stages: Optional[int] = None,
    pp_microbatches: Optional[int] = None,
    pp_schedule: Optional[str] = None,
    pp_interleave: Optional[int] = None,
    pp_quantized: Optional[bool] = None,
    moe_experts: Optional[int] = None,
    moe_topk: Optional[int] = None,
    moe_capacity: Optional[float] = None,
    moe_quantized: Optional[bool] = None,
) -> StepPlan:
    """Resolve today's knob combination into its :class:`StepPlan` — the
    debug view of what the gradient wire will compile to.

    Unset knobs resolve exactly like ``DistributedOptimizer`` resolves
    them (``tuned_params`` override first, then the init-time Config /
    ``HOROVOD_*`` env). ``mesh_shape`` defaults to the live mesh
    (``(cross, local[, pods])``), or ``(1, 1)`` before init."""
    if tuned_params is not None:
        if fusion_threshold_bytes is None:
            fusion_threshold_bytes = tuned_params.fusion_threshold_bytes
        if hierarchical is None:
            hierarchical = tuned_params.hierarchical_allreduce
        if zero_stage is None:
            zero_stage = tuned_params.zero_stage
        if overlap is None:
            overlap = tuned_params.overlap
        if num_comm_streams is None:
            num_comm_streams = tuned_params.num_comm_streams
        if quant_block is None:
            quant_block = tuned_params.quant_block
        if pp_microbatches is None:
            pp_microbatches = getattr(tuned_params, "pp_microbatches",
                                      None) or None
        if pp_interleave is None:
            pp_interleave = getattr(tuned_params, "pp_interleave",
                                    None) or None
        if moe_capacity is None:
            moe_capacity = getattr(tuned_params, "moe_capacity_factor",
                                   0.0) or None
        if moe_quantized is None and getattr(
                tuned_params, "moe_capacity_factor", 0.0):
            moe_quantized = getattr(tuned_params, "moe_quantized", None)
    cfg = basics.config() if basics.is_initialized() else None
    if quantized is None:
        quantized = (cfg.quantized_allreduce if cfg is not None
                     else _env_bool("HOROVOD_QUANTIZED_ALLREDUCE", False))
    if zero_stage is None and zero is not None:
        zero_stage = 2 if zero else 0
    if zero_stage is None:
        from ..parallel.optimizer import _resolve_zero_stage_config

        zero_stage = _resolve_zero_stage_config()
    if zero_stage not in (0, 1, 2, 3):
        raise PlanError(f"zero_stage must be 0..3, got {zero_stage!r}")
    if overlap is None:
        overlap = (cfg.overlap if cfg is not None
                   else _env_bool("HOROVOD_OVERLAP", False))
    if hierarchical is None:
        hierarchical = (cfg.hierarchical_allreduce if cfg is not None
                        else _env_bool("HOROVOD_HIERARCHICAL_ALLREDUCE",
                                       False))
    if num_comm_streams is None:
        num_comm_streams = (cfg.num_comm_streams if cfg is not None
                            else _env_int("HOROVOD_NUM_COMM_STREAMS", 1))
    if quant_block is None:
        quant_block = (cfg.quant_block if cfg is not None
                       else _env_int("HOROVOD_QUANT_BLOCK", 256))
    if fusion_threshold_bytes is None:
        fusion_threshold_bytes = (
            cfg.fusion_threshold_bytes if cfg is not None
            else _env_int("HOROVOD_FUSION_THRESHOLD", 64 * 1024 * 1024))
    if mesh_shape is None:
        if basics.is_initialized() and basics.mesh() is not None:
            # The DATA mesh: a pipeline mesh's leading hvd_pp dim feeds
            # pp_stages below, never the collective level ladder.
            mesh_shape = basics.data_mesh_shape()
        else:
            mesh_shape = (1, 1)
    if pp_stages is None:
        if basics.is_initialized() and basics.mesh() is not None:
            pp_stages = basics.pp_size()
        else:
            pp_stages = (cfg.pp_stages if cfg is not None
                         else _env_int("HOROVOD_PP_STAGES", 0))
    pp_stages = int(pp_stages or 0)
    if pp_schedule is None:
        pp_schedule = (cfg.pp_schedule if cfg is not None
                       else "interleaved_1f1b")
    if pp_interleave is None:
        pp_interleave = (cfg.pp_interleave if cfg is not None else 1) or 1
    if pp_microbatches is None:
        pp_microbatches = (cfg.pp_microbatches if cfg is not None else 0)
    if not pp_microbatches:
        pp_microbatches = 2 * pp_stages  # schedule default (pow2-ish)
    if pp_quantized is None:
        pp_quantized = (cfg.pp_quantized if cfg is not None
                        else _env_bool("HOROVOD_PP_QUANTIZED", False))
    if moe_experts is None:
        if basics.is_initialized() and basics.mesh() is not None \
                and basics.ep_size() > 1:
            moe_experts = basics.ep_size()
        else:
            moe_experts = (cfg.moe_experts if cfg is not None
                           else _env_int("HOROVOD_MOE_EXPERTS", 0))
    moe_experts = int(moe_experts or 0)
    if moe_topk is None:
        moe_topk = (cfg.moe_topk if cfg is not None
                    else _env_int("HOROVOD_MOE_TOPK", 2))
    if moe_capacity is None:
        moe_capacity = (cfg.moe_capacity_factor if cfg is not None
                        else 1.25)
    if moe_quantized is None:
        moe_quantized = (cfg.moe_quantized if cfg is not None
                         else _env_bool("HOROVOD_MOE_QUANTIZED", False))
    quantized_pod = _resolve_quantized_pod(quantized_pod)
    nl, nc, npod = _mesh_sizes(mesh_shape)
    # The level ladder is structural, not size-gated: a 1-host mesh still
    # derives the 2-level plan (its DCN legs lower to no-ops at size 1).
    levels = [ICI, DCN] + ([POD] if npod > 1 else [])
    ef = quantized if error_feedback is None else error_feedback
    streams = max(1, int(num_comm_streams)) if overlap else 1
    overlap = bool(overlap)

    if zero_stage > 0:
        gradient = derive_reduce_scatter(
            levels=levels, quantized=quantized, error_feedback=ef,
            block=quant_block if quantized else None, streams=streams,
            overlap=overlap)
        gather = derive_all_gather(
            levels=levels, quantized=quantized, error_feedback=ef,
            block=quant_block if quantized else None, streams=streams,
            overlap=overlap)
    else:
        gradient = derive_allreduce(
            levels=levels, quantized=quantized,
            hierarchical=hierarchical,
            block=quant_block if (quantized or quantized_pod) else None,
            error_feedback=ef, streams=streams, overlap=overlap,
            quantized_pod=quantized_pod)
        gather = None
    send = None
    if pp_stages > 1:
        send = derive_send(mesh_shape=mesh_shape,
                           quantized=bool(pp_quantized),
                           block=quant_block if pp_quantized else None)
    moe = None
    if moe_experts > 1:
        moe = derive_a2a(mesh_shape=mesh_shape,
                         quantized=bool(moe_quantized),
                         block=quant_block if moe_quantized else None)
    return StepPlan(
        moe=moe,
        moe_experts=moe_experts if moe_experts > 1 else 0,
        moe_topk=int(moe_topk) if moe_experts > 1 else 0,
        moe_capacity_factor=(float(moe_capacity)
                             if moe_experts > 1 else 0.0),
        moe_quantized=(bool(moe_quantized) and moe is not None
                       and moe.is_quantized),
        send=send,
        pp_stages=pp_stages if pp_stages > 1 else 0,
        pp_microbatches=int(pp_microbatches) if pp_stages > 1 else 0,
        pp_schedule=str(pp_schedule),
        pp_interleave=max(1, int(pp_interleave)),
        mesh_shape=tuple(int(v) for v in mesh_shape),
        quantized=bool(quantized),
        quant_block=int(quant_block),
        zero_stage=int(zero_stage),
        overlap=overlap,
        hierarchical=bool(hierarchical),
        num_comm_streams=int(num_comm_streams),
        fusion_threshold_bytes=int(fusion_threshold_bytes),
        gradient=gradient,
        gather=gather,
        quantized_pod=bool(quantized_pod),
    )


# ---------------------------------------------------------------------------
# Autotune plan encoding: the compact search-space string the GP proposes
# over (cache schema v5, docs/autotune.md). Round-trips through
# decode_tuned; tolerant of absence in pre-v5 logs/caches.
# ---------------------------------------------------------------------------

_PLAN_RE = re.compile(
    r"^(?P<grad>ar\.flat|ar\.tree|rs\+ag\.z[123])\|"
    r"(?P<wire>fp|int8/\d+)\|s(?P<streams>\d+)\|(?P<sched>sync|ovl)"
    r"(\|pp(?P<ppm>\d+)/(?P<ppv>\d+)(?P<ppzb>\|zb1)?)?"
    r"(\|moe(?P<moecap>[0-9.]+)/(?P<moeq>q8|fp))?"
    r"(\|sv(?P<svk>\d+)/(?P<svq>q8|fp))?$")


def encode_tuned(params, *, quantized: bool = False,
                 pp: bool = False, moe: bool = False,
                 serve: bool = False) -> str:
    """Compact plan encoding of a ``TunedParams``-like knob set: gradient
    leg order | DCN hop wire dtype | stream count | placement. E.g.
    ``ar.tree|int8/256|s2|ovl`` or ``rs+ag.z2|int8/256|s1|sync``. Knob
    sets that compile to the same wire encode identically
    (``hierarchical`` is dead under ZeRO's rs+ag split and drops out)."""
    stage = int(getattr(params, "zero_stage", 0) or 0)
    if stage > 0:
        grad = f"rs+ag.z{stage}"
    elif getattr(params, "hierarchical_allreduce", False):
        grad = "ar.tree"
    else:
        grad = "ar.flat"
    wire = (f"int8/{int(getattr(params, 'quant_block', 256))}"
            if quantized else "fp")
    streams = int(getattr(params, "num_comm_streams", 1) or 1)
    sched = "ovl" if getattr(params, "overlap", False) else "sync"
    if sched == "sync":
        streams = 1  # dead knob with overlap off: same wire, one trial
    enc = f"{grad}|{wire}|s{streams}|{sched}"
    if pp:
        # Schema v8 (docs/pipeline.md): the pipeline schedule knobs —
        # microbatch count / interleave degree — join the plan encoding
        # only when the session's step is pipelined; with pp off both
        # are dead knobs and drop out (one trial, not four).
        m = int(getattr(params, "pp_microbatches", 0) or 0)
        v = max(1, int(getattr(params, "pp_interleave", 1) or 1))
        enc += f"|pp{m}/{v}"
        # Schema v11 (docs/pipeline.md): the zero-bubble family marker —
        # present only when the tuned schedule is zb1 (so every v10
        # encoding is also a valid v11 encoding); with pp off the
        # schedule is a dead knob and collapses to interleaved-1F1B.
        if str(getattr(params, "pp_schedule", "") or "") == "zb1":
            enc += "|zb1"
    if moe:
        # Schema v9 (docs/moe.md): the MoE routing knobs — dispatch
        # capacity factor / a2a wire dtype — join the plan encoding only
        # when the session's step carries an MoE layer; with moe off
        # both are dead knobs and drop out (one trial, not four).
        cap = float(getattr(params, "moe_capacity_factor", 0.0) or 0.0)
        if cap <= 0.0:
            cap = 1.25  # the config default: moe on needs a capacity
        q = "q8" if getattr(params, "moe_quantized", False) else "fp"
        enc += f"|moe{cap:g}/{q}"
    if serve:
        # Schema v10 (docs/serving.md): the disaggregated-serving knobs —
        # speculative draft length / KV-migration wire dtype — join the
        # plan encoding only when the session tunes a serving engine;
        # in a training session both are dead knobs and drop out.
        k = int(getattr(params, "spec_draft_k", 0) or 0)
        q = "q8" if getattr(params, "kv_migrate_quantized", False) else "fp"
        enc += f"|sv{k}/{q}"
    return enc


# ---------------------------------------------------------------------------
# Plan-space enumeration + analytic shortlist (docs/cost-model.md): the
# legal plan space of a knob set, priced by the cost model into a ranked
# shortlist the GP autotuner warm-starts from.
# ---------------------------------------------------------------------------

# Fusion-threshold candidates: small enough that the alpha term prices
# bucketing, large enough to span the search box (1-256 MiB, log-space).
_DEFAULT_THRESHOLDS = (4 * 1024 * 1024, 16 * 1024 * 1024,
                       64 * 1024 * 1024)
_DEFAULT_BLOCKS = (128, 256, 512)


@dataclasses.dataclass(frozen=True)
class PricedPlan:
    """One shortlist row: a knob setting (``params`` is an
    ``autotune.TunedParams``), the :class:`StepPlan` it derives, and its
    :class:`~horovod_tpu.plan.cost.StepCost`."""

    params: object
    plan: StepPlan
    cost: object

    @property
    def predicted_ms(self) -> float:
        return self.cost.predicted_ms

    def as_dict(self) -> dict:
        return {"plan": self.plan.encode(),
                "predicted_ms": round(self.cost.predicted_ms, 6),
                "modeled_ms": round(self.cost.modeled_ms, 6),
                "params": self.params.as_dict()}


_DEFAULT_MOE_CAPS = (1.0, 1.25, 1.5, 2.0)


def enumerate_tuned(*, quantized: bool = False,
                    tune_hierarchical: bool = True,
                    tune_zero: bool = False,
                    tune_overlap: bool = False,
                    tune_pp: bool = False,
                    pp_stages: int = 0,
                    pp_max_interleave: int = 1,
                    tune_moe: bool = False,
                    moe_experts: int = 0,
                    initial=None,
                    thresholds=None,
                    blocks=None) -> list:
    """Enumerate the legal knob space of one tuning session as
    ``TunedParams`` candidates: leg order (flat/tree vs the ZeRO rs+ag
    split) x DCN wire dtype scale block x stream split x fusion
    threshold — gated exactly like the autotuner's search
    dimensions (a knob the session's step cannot accept is pinned to the
    initial value), deduplicated on the canonical plan encoding so knob
    sets that compile to the same wire appear once."""
    from ..autotune.parameter_manager import TunedParams

    if initial is None:
        initial = TunedParams()
    thr_opts = sorted(
        {int(t) for t in (thresholds or _DEFAULT_THRESHOLDS)}
        | {int(initial.fusion_threshold_bytes)})
    blk_opts = (sorted({int(b) for b in (blocks or _DEFAULT_BLOCKS)}
                       | {int(initial.quant_block)})
                if quantized else (int(initial.quant_block),))
    stage_opts = (0, 1, 2) if tune_zero else (initial.zero_stage,)
    if tune_pp and pp_stages > 1:
        # Pipeline candidates (docs/pipeline.md): pow2-ish microbatch
        # counts that divide by the stage count, crossed with the legal
        # interleave degrees — the bubble/alpha tradeoff the cost model
        # prices (more microbatches shrink the bubble, cost more send
        # launches).
        ppm_opts = sorted({pp_stages, 2 * pp_stages, 4 * pp_stages}
                          | ({int(initial.pp_microbatches)}
                             if initial.pp_microbatches else set()))
        ppv_opts = sorted({v for v in (1, 2, 4)
                           if v <= max(1, pp_max_interleave)})
        # Schedule family (v11, docs/pipeline.md): the zero-bubble B/W
        # split trades more send launches per tick grid for a strictly
        # smaller bubble — a real candidate axis, not a dead knob.
        ppsched_opts = ("interleaved_1f1b", "zb1")
    else:
        ppm_opts = (initial.pp_microbatches,)
        ppv_opts = (initial.pp_interleave,)
        ppsched_opts = (str(getattr(initial, "pp_schedule",
                                    "interleaved_1f1b")
                            or "interleaved_1f1b"),)
    if tune_moe and moe_experts > 1:
        # MoE candidates (docs/moe.md): the capacity/wire tradeoff the
        # cost model prices — a higher capacity factor drops fewer
        # tokens but moves a proportionally bigger dispatch buffer; the
        # int8 a2a wire buys bytes at quantize-kernel cost.
        init_cap = float(getattr(initial, "moe_capacity_factor", 0.0)
                         or 0.0)
        cap_opts = sorted(set(_DEFAULT_MOE_CAPS)
                          | ({init_cap} if init_cap > 0 else set()))
        moeq_opts = (False, True)
    else:
        cap_opts = (getattr(initial, "moe_capacity_factor", 0.0),)
        moeq_opts = (getattr(initial, "moe_quantized", False),)
    out, seen = [], set()
    for thr in thr_opts:
        for blk in blk_opts:
            for stage in stage_opts:
                if stage == 0:
                    hier_opts = ((False, True) if tune_hierarchical
                                 else (initial.hierarchical_allreduce,))
                else:
                    hier_opts = (False,)  # dead under the rs+ag split
                for hier in hier_opts:
                    ovl_opts = ((False, True) if tune_overlap
                                else (bool(initial.overlap),))
                    for ovl in ovl_opts:
                        if not ovl:
                            stream_opts = (1,)
                        elif tune_overlap:
                            stream_opts = (1, 2, 4)
                        else:
                            stream_opts = (
                                max(1, initial.num_comm_streams),)
                        for s in stream_opts:
                            for ppm in ppm_opts:
                                for ppv in ppv_opts:
                                    for pps in ppsched_opts:
                                        for cap in cap_opts:
                                            for mq in moeq_opts:
                                                p = TunedParams(
                                                    fusion_threshold_bytes=thr,
                                                    quant_block=blk,
                                                    hierarchical_allreduce=hier,
                                                    zero_stage=stage,
                                                    overlap=ovl,
                                                    num_comm_streams=s,
                                                    pp_microbatches=ppm,
                                                    pp_interleave=ppv,
                                                    pp_schedule=pps,
                                                    moe_capacity_factor=cap,
                                                    moe_quantized=mq)
                                                key = (thr, blk,
                                                       encode_tuned(
                                                           p,
                                                           quantized=quantized,
                                                           pp=tune_pp,
                                                           moe=tune_moe))
                                                if key in seen:
                                                    continue
                                                seen.add(key)
                                                out.append(p)
    return out


def shortlist(payload_bytes: float, *, itemsize: float = 4.0,
              mesh_shape=None, model=None, compute_ms=None,
              quantized: bool = False, k: Optional[int] = None,
              tune_hierarchical: bool = True, tune_zero: bool = False,
              tune_overlap: bool = False,
              tune_pp: bool = False, pp_stages: int = 0,
              pp_max_interleave: int = 1,
              tune_moe: bool = False, moe_experts: int = 0,
              initial=None, thresholds=None, blocks=None) -> list:
    """Enumerate, validate, and PRICE the legal plan space for a knob
    set, returning :class:`PricedPlan` rows ranked by predicted step-
    wire milliseconds (ties broken by the stable plan encoding).

    Every candidate is filtered through ``WirePlan.validate`` (via
    :func:`describe_plan`'s constructors); ``model`` defaults to the
    calibrated cost model when a matching-geometry sweep is stored,
    else the static env triples (:func:`horovod_tpu.plan.cost.resolve`).
    ``k`` truncates to the top-K (None = the full ranked space) — the
    autotuner's warm-start seeds (docs/cost-model.md)."""
    from . import cost as _cost

    if mesh_shape is None:
        if basics.is_initialized() and basics.mesh() is not None:
            mesh_shape = basics.data_mesh_shape()
        else:
            mesh_shape = (1, 1)
    model = model or _cost.resolve(mesh_shape)
    priced = []
    seen = set()
    for p in enumerate_tuned(quantized=quantized,
                             tune_hierarchical=tune_hierarchical,
                             tune_zero=tune_zero,
                             tune_overlap=tune_overlap,
                             tune_pp=tune_pp, pp_stages=pp_stages,
                             pp_max_interleave=pp_max_interleave,
                             tune_moe=tune_moe, moe_experts=moe_experts,
                             initial=initial,
                             thresholds=thresholds, blocks=blocks):
        try:
            sp = describe_plan(tuned_params=p, quantized=quantized,
                               mesh_shape=mesh_shape,
                               quantized_pod=False,
                               pp_stages=(pp_stages if tune_pp
                                          else None),
                               moe_experts=(moe_experts if tune_moe
                                            else 0),
                               moe_quantized=(p.moe_quantized
                                              if tune_moe else None))
        except PlanError:
            continue  # illegal composition: not a candidate
        # Dedup on the DERIVED wire (plus the threshold, ZeRO stage,
        # and MoE capacity factor, which the encoding does not carry —
        # stages 1/2 share a wire but restructure the accumulator, and
        # the capacity factor reshapes the dispatch buffer): knobs dead
        # in this knob set's derivation (e.g. hierarchical under a
        # quantized 2-level wire) must not spend two shortlist rows on
        # one compiled program.
        key = (sp.encode(), int(p.fusion_threshold_bytes),
               int(p.zero_stage),
               float(p.moe_capacity_factor) if tune_moe else 0.0)
        if key in seen:
            continue
        seen.add(key)
        sc = _cost.price_step(sp, payload_bytes, itemsize=itemsize,
                              mesh_shape=mesh_shape, model=model,
                              compute_ms=compute_ms)
        priced.append(PricedPlan(p, sp, sc))
    priced.sort(key=lambda pp: (pp.predicted_ms, pp.plan.encode()))
    return priced[:k] if k else priced


def decode_tuned(encoding: str) -> dict:
    """Parse a plan encoding back to the knob dict it derives from.
    Raises :class:`PlanError` on malformed input (tolerant readers catch
    it and fall back to the explicit knob columns)."""
    m = _PLAN_RE.match(encoding.strip())
    if not m:
        raise PlanError(
            f"unparseable plan encoding {encoding!r} — expected "
            f"'<ar.flat|ar.tree|rs+ag.zN>|<fp|int8/B>|sK|<sync|ovl>'")
    grad = m.group("grad")
    out = {
        "zero_stage": int(grad[-1]) if grad.startswith("rs+ag") else 0,
        "hierarchical_allreduce": grad == "ar.tree",
        "quantized": m.group("wire") != "fp",
        "overlap": m.group("sched") == "ovl",
        "num_comm_streams": int(m.group("streams")),
        "pp_microbatches": int(m.group("ppm") or 0),
        "pp_interleave": int(m.group("ppv") or 1),
        # v11: |zb1 rides the pp segment — absent (or pp off) decodes
        # to the interleaved-1F1B default, so zb collapses to 1f1b
        # whenever the pipeline knobs are dead.
        "pp_schedule": ("zb1" if m.group("ppzb")
                        else "interleaved_1f1b"),
        "moe_capacity_factor": float(m.group("moecap") or 0.0),
        "moe_quantized": m.group("moeq") == "q8",
        "spec_draft_k": int(m.group("svk") or 0),
        "kv_migrate_quantized": m.group("svq") == "q8",
    }
    if out["quantized"]:
        out["quant_block"] = int(m.group("wire").split("/", 1)[1])
    return out
