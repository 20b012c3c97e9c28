"""The wire-plan IR: a collective as an ordered list of per-level legs.

Following HiCCL (arXiv:2408.05962), a collective over a machine hierarchy
is best expressed as a *composition of per-level primitives* rather than a
monolithic hand-written path: an allreduce over a TPU pod is an intra-host
reduce-scatter (ICI), a cross-host reduction (DCN), and an intra-host
all-gather — and a quantized allreduce (EQuARX, arXiv:2506.17615) is the
SAME composition with an int8 wire dtype attribute on the DCN hops, not a
separate code path.

The IR is deliberately tiny:

* a :class:`Leg` names a mesh **level** (``ici`` ring / ``dcn`` cross /
  ``pod`` axis, or ``flat`` for one XLA-decomposed collective over the
  whole axis tuple), a **primitive** (``reduce_scatter`` / ``all_gather``
  / ``all_to_all`` / ``psum``), a **wire dtype** (``payload`` or
  blockwise-``int8`` with an fp32 scale per ``block`` elements and an
  optional error-feedback slot), and a **stream** assignment;
* a :class:`WirePlan` is an ordered leg tuple plus the stream/overlap
  placement for the whole collective.

Plans are *validated data*, not code: :meth:`WirePlan.validate` rejects
illegal compositions (a reduce leg after the gather phase began, int8 on
a non-DCN hop, a non-power-of-two stream count) with actionable messages,
and the compiler (:mod:`horovod_tpu.plan.compiler`) lowers a validated
plan to the existing jax primitives. The planner
(:mod:`horovod_tpu.plan.planner`) derives the default plan from today's
knob set, so every (quantized, zero_stage, overlap, hierarchical) knob
combination is one point in plan space.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# Mesh levels a leg can ride. ``flat`` is the degenerate single-leg plan:
# one collective over the whole axis tuple, letting XLA's topology-aware
# decomposition place the ICI/DCN traffic itself.
ICI = "ici"
DCN = "dcn"
POD = "pod"
FLAT = "flat"
LEVELS = (ICI, DCN, POD, FLAT)

# Per-leg primitives (the HiCCL composition alphabet, restricted to what
# the TPU lowerings use). ``send`` is the point-to-point primitive of the
# pipeline wire (docs/pipeline.md): one ``lax.ppermute`` hop carrying an
# inter-stage activation (or activation-grad) along the hvd_pp axis,
# charged to the link class its ``level`` names. The same primitive also
# carries the ``kv_migrate`` plan family (docs/serving.md): one
# prefill→decode KV-page handoff between serving replicas, lowered
# host-side between two engine meshes rather than as an in-program
# collective. ``all_to_all`` is the MoE dispatch/combine primitive
# (docs/moe.md): one tiled ``lax.all_to_all`` row exchange along the
# hvd_ep axis, owned by the ``a2a`` plan family.
REDUCE_SCATTER = "reduce_scatter"
ALL_GATHER = "all_gather"
ALL_TO_ALL = "all_to_all"
PSUM = "psum"
SEND = "send"
PRIMITIVES = (REDUCE_SCATTER, ALL_GATHER, ALL_TO_ALL, PSUM, SEND)

# Wire dtypes. ``payload`` rides whatever dtype the caller handed the
# collective (after any Compression cast); ``int8`` is the blockwise-
# scaled int8 wire with one fp32 scale per ``block`` elements.
PAYLOAD = "payload"
BF16 = "bf16"
INT8 = "int8"
WIRE_DTYPES = (PAYLOAD, BF16, INT8)

# Link class each per-level leg is charged to by the accounting and the
# cost model (docs/cost-model.md). The flat leg decomposes into all of
# them — its accounting/pricing rows carry the hop explicitly.
LEVEL_HOP = {ICI: "ici", DCN: "dcn", POD: "pod"}

_REDUCE_PRIMS = (REDUCE_SCATTER, PSUM)
_GATHER_PRIMS = (ALL_GATHER,)

_COLLECTIVES = ("allreduce", "reduce_scatter", "all_gather", "send",
                "a2a", "kv_migrate")

# Plan families whose legs are point-to-point ``send`` hops rather than
# reduction/gather ladder rungs: the pipeline wire and the serving KV
# handoff share the primitive but differ in who lowers them (in-program
# ppermute vs host-side replica-to-replica transfer).
_SEND_COLLECTIVES = ("send", "kv_migrate")


class PlanError(ValueError):
    """A wire plan failed validation (illegal leg composition)."""


@dataclasses.dataclass(frozen=True)
class Leg:
    """One hop of a wire plan: a primitive at a mesh level.

    ``wire_dtype``/``block`` describe the bytes on THIS hop only (the
    EQuARX rule: dtype transforms are per-hop attributes, and int8 is
    only legal on the slow DCN hop — the ICI leg always rides the
    payload dtype). ``error_feedback`` marks the hop as carrying an
    error-feedback residual slot (the quantization error of what this
    rank sent, re-injected next step). ``stream`` is the comm-stream
    slot the leg's bucket collective is issued on when the plan is
    overlap-scheduled (0-based, < :attr:`WirePlan.streams`).
    """

    level: str
    primitive: str
    wire_dtype: str = PAYLOAD
    block: Optional[int] = None
    error_feedback: bool = False
    stream: int = 0

    def describe(self) -> str:
        d = self.wire_dtype
        if self.wire_dtype == INT8 and self.block:
            d = f"int8/{self.block}"
        if self.error_feedback:
            d += "+ef"
        return f"{self.level}.{self.primitive}[{d}]"


@dataclasses.dataclass(frozen=True)
class WirePlan:
    """An ordered leg composition for one collective.

    ``streams`` is the flight width of the overlap schedule (how many
    bucket collectives sit in the program with no consumer between
    them); ``overlap`` marks the plan for reverse-layer stream placement
    (:func:`horovod_tpu.ops.fusion.stream_order`). Neither changes the
    math — they are placement attributes, which is why overlap-on is
    bit-identical to off (docs/overlap.md).
    """

    collective: str
    legs: Tuple[Leg, ...]
    streams: int = 1
    overlap: bool = False

    # -- structure queries (the compiler and planner dispatch on these) --

    @property
    def is_flat(self) -> bool:
        return len(self.legs) == 1 and self.legs[0].level == FLAT

    @property
    def is_quantized(self) -> bool:
        return any(l.wire_dtype == INT8 for l in self.legs)

    @property
    def is_dcn_quantized(self) -> bool:
        """Int8 on the cross-host (DCN) hop — the wire the 2-level
        quantized lowerings (lower_quantized_allreduce, the ZeRO rs/ag
        legs) compress. A plan whose only int8 legs ride the POD level
        (the quantized pod hop) is NOT dcn-quantized: it lowers through
        the tree ladder, which owns the pod legs."""
        return any(l.wire_dtype == INT8 and l.level == DCN
                   for l in self.legs)

    @property
    def is_tree(self) -> bool:
        """A multi-leg hierarchical (per-level) composition."""
        return not self.is_flat and len(self.legs) > 1

    @property
    def levels(self) -> Tuple[str, ...]:
        return tuple(l.level for l in self.legs)

    @property
    def quant_block(self) -> Optional[int]:
        for l in self.legs:
            if l.wire_dtype == INT8 and l.block:
                return l.block
        return None

    def encode(self) -> str:
        """Compact one-line encoding — legs joined with ``>`` plus the
        stream placement suffix. Stable: the autotuner's CSV/cache plan
        column and the golden-text plan dumps both use it."""
        body = ">".join(l.describe() for l in self.legs)
        tail = f"|s{self.streams}|{'ovl' if self.overlap else 'sync'}"
        return f"{self.collective}:{body}{tail}"

    # -- validation ------------------------------------------------------

    def validate(self) -> "WirePlan":
        """Check the composition; raises :class:`PlanError` with an
        actionable message on the first violation. Returns self so
        ``WirePlan(...).validate()`` chains."""
        if self.collective not in _COLLECTIVES:
            raise PlanError(
                f"unknown collective {self.collective!r}: a wire plan "
                f"compiles one of {_COLLECTIVES}")
        if not self.legs:
            raise PlanError(
                f"empty {self.collective} plan: a plan needs at least "
                f"one leg (use a single flat leg for the XLA-decomposed "
                f"default)")
        if self.streams not in (1, 2, 4):
            raise PlanError(
                f"stream count {self.streams} is invalid: comm streams "
                f"must be a power of two in 1..4 "
                f"(HOROVOD_NUM_COMM_STREAMS contract, docs/overlap.md)")
        for i, leg in enumerate(self.legs):
            where = f"leg {i} ({leg.level}.{leg.primitive})"
            if leg.level not in LEVELS:
                raise PlanError(
                    f"{where}: unknown level {leg.level!r} — levels are "
                    f"{LEVELS} (ici=intra-host ring, dcn=cross-host, "
                    f"pod=cross-pod, flat=whole axis tuple)")
            if leg.primitive not in PRIMITIVES:
                raise PlanError(
                    f"{where}: unknown primitive {leg.primitive!r} — "
                    f"primitives are {PRIMITIVES}")
            if leg.wire_dtype not in WIRE_DTYPES:
                raise PlanError(
                    f"{where}: unknown wire dtype {leg.wire_dtype!r} — "
                    f"wire dtypes are {WIRE_DTYPES}")
            if leg.wire_dtype == INT8 and leg.level not in (DCN, POD):
                raise PlanError(
                    f"{where}: blockwise-int8 wire dtype on a non-DCN "
                    f"hop — compression belongs on the slow cross-host "
                    f"links only; the ICI leg always rides the payload "
                    f"dtype (HiCCL placement rule, docs/wire-plan.md)")
            if leg.wire_dtype == INT8 and leg.primitive == PSUM:
                raise PlanError(
                    f"{where}: blockwise-int8 on a psum leg — int8 "
                    f"blocks with per-block scales are not closed under "
                    f"addition, so the exact psum has no quantized "
                    f"lowering; spell a quantized hop as the "
                    f"reduce_scatter[int8] > all_gather[int8] pair "
                    f"(the quantized pod hop, docs/wire-plan.md)")
            if ((leg.primitive == SEND)
                    != (self.collective in _SEND_COLLECTIVES)):
                if leg.primitive == SEND:
                    raise PlanError(
                        f"{where}: a send leg only belongs to a 'send' "
                        f"or 'kv_migrate' plan — the point-to-point hop "
                        f"does not compose with reduction/gather "
                        f"ladders (docs/pipeline.md, docs/serving.md)")
                raise PlanError(
                    f"{where}: a {self.collective} plan carries only "
                    f"send legs, got {leg.primitive!r} — the point-to-"
                    f"point wire is one hop per direction "
                    f"(docs/pipeline.md, docs/serving.md)")
            if leg.primitive == SEND and leg.level == FLAT:
                raise PlanError(
                    f"{where}: a send leg names the LINK CLASS the "
                    f"pipeline hop crosses (ici/dcn/pod) — there is no "
                    f"flat decomposition of a point-to-point hop")
            if (leg.primitive == ALL_TO_ALL) != (self.collective == "a2a"):
                if leg.primitive == ALL_TO_ALL:
                    raise PlanError(
                        f"{where}: an all_to_all leg only belongs to an "
                        f"'a2a' plan — the MoE dispatch/combine exchange "
                        f"is a permutation, not a reduction/gather "
                        f"ladder (docs/moe.md)")
                raise PlanError(
                    f"{where}: an a2a plan carries only all_to_all "
                    f"legs, got {leg.primitive!r} — the MoE wire is one "
                    f"tiled row exchange per direction (docs/moe.md)")
            if leg.primitive == ALL_TO_ALL and leg.level == FLAT:
                raise PlanError(
                    f"{where}: an a2a leg names the LINK CLASS the "
                    f"expert-parallel hop crosses (ici/dcn/pod) — there "
                    f"is no flat decomposition of the hvd_ep row "
                    f"exchange (docs/moe.md)")
            if leg.error_feedback and leg.level not in (DCN, POD):
                raise PlanError(
                    f"{where}: error-feedback slot on a non-DCN hop — "
                    f"EF accumulates the quantization error of the "
                    f"compressed cross-host wire; exact ICI legs have "
                    f"no error to feed back")
            if leg.block is not None and leg.wire_dtype != INT8:
                raise PlanError(
                    f"{where}: scale block {leg.block} without an int8 "
                    f"wire dtype — block is the int8 scale granularity")
            if leg.block is not None and leg.block < 1:
                raise PlanError(
                    f"{where}: scale block must be >= 1, got {leg.block}")
            if not (0 <= leg.stream < self.streams):
                raise PlanError(
                    f"{where}: stream {leg.stream} out of range for a "
                    f"{self.streams}-stream plan (streams are 0-based "
                    f"flight slots)")
            if leg.level == FLAT and len(self.legs) > 1:
                raise PlanError(
                    f"{where}: a flat leg is the WHOLE plan (one "
                    f"XLA-decomposed collective over the full axis "
                    f"tuple) — it cannot compose with per-level legs")
        self._validate_order()
        return self

    def _validate_order(self) -> None:
        prims = [(l.level, l.primitive) for l in self.legs]
        if self.collective == "allreduce":
            # Reduce phase (reduce_scatter / psum / all_to_all) first,
            # gather phase (all_gather) after; every level scattered must
            # be re-gathered in mirror (LIFO) order.
            gather_started = False
            scattered: list = []
            gathered: list = []
            for i, (level, prim) in enumerate(prims):
                if prim in _GATHER_PRIMS:
                    gather_started = True
                    gathered.append(level)
                elif gather_started:
                    raise PlanError(
                        f"illegal leg order in {self.encode()}: leg {i} "
                        f"({level}.{prim}) is a reduce leg after the "
                        f"gather phase began — an allreduce plan must "
                        f"finish its reduction ladder before re-"
                        f"gathering (scatter down, gather back up)")
                if prim == REDUCE_SCATTER and level != FLAT:
                    scattered.append(level)
            if scattered and gathered != list(reversed(scattered)):
                raise PlanError(
                    f"unbalanced allreduce plan {self.encode()}: levels "
                    f"reduce-scattered {scattered} must be re-gathered "
                    f"in mirror order, got gathers {gathered} — the "
                    f"output would not be the full replicated sum")
        elif self.collective == "reduce_scatter":
            for i, (level, prim) in enumerate(prims):
                if prim in _GATHER_PRIMS:
                    raise PlanError(
                        f"illegal leg in {self.encode()}: leg {i} "
                        f"({level}.{prim}) — a reduce_scatter plan ends "
                        f"holding 1/world shards; an all_gather leg "
                        f"belongs to the all_gather plan (the ZeRO wire "
                        f"splits the allreduce in half around the "
                        f"optimizer update)")
        elif self.collective == "send":
            if len(self.legs) != 1:
                raise PlanError(
                    f"illegal send plan {self.encode()}: a send plan is "
                    f"exactly ONE hop (one ppermute leg on one link "
                    f"class) — the pipeline schedule composes hops by "
                    f"issuing one plan per direction, docs/pipeline.md")
        elif self.collective == "kv_migrate":
            if len(self.legs) != 1:
                raise PlanError(
                    f"illegal kv_migrate plan {self.encode()}: a KV "
                    f"migration is exactly ONE hop (one send leg on the "
                    f"link class the prefill→decode handoff crosses) — "
                    f"the migrator streams a whole slot's pages through "
                    f"one wire, docs/serving.md")
        elif self.collective == "a2a":
            if len(self.legs) != 1:
                raise PlanError(
                    f"illegal a2a plan {self.encode()}: an a2a plan is "
                    f"exactly ONE exchange (one all_to_all leg on one "
                    f"link class) — the MoE layer composes the wire by "
                    f"issuing one plan per direction (dispatch, then "
                    f"combine), docs/moe.md")
        elif self.collective == "all_gather":
            for i, (level, prim) in enumerate(prims):
                if prim not in _GATHER_PRIMS and level != FLAT:
                    raise PlanError(
                        f"illegal leg in {self.encode()}: leg {i} "
                        f"({level}.{prim}) — an all_gather plan only "
                        f"concatenates shards; reductions belong to the "
                        f"reduce_scatter/allreduce plans")
