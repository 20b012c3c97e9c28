"""Span auditing over Timeline files: B/E balance and phase durations.

One helper for the invariant every span-emitting subsystem must hold —
each ``ph:"B"`` has a matching ``ph:"E"`` on the same tid and depth never
goes negative — plus the per-activity duration accounting that
``scripts/obs_report.py`` turns into the phase-time breakdown. Replaces
the hand-rolled balance loops that used to live in ``tests/test_overlap``
and ``tests/test_serve``.

The event vocabulary is a CHECKED table (:data:`KNOWN_PREFIXES`,
docs/observability.md has the full event table): every family a
subsystem emits is registered here, and ``audit_spans(strict=True)``
fails on an event whose prefix is not — so a typo'd span name (or a new
family someone forgot to document) breaks the span tests instead of
silently skewing a phase breakdown.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

#: The unified Timeline event vocabulary: every ``PREFIX:`` an event
#: family may use (plus the colon-free reference-parity cycle marker).
#: One row per family in docs/observability.md's event table; new
#: subsystems register here FIRST.
KNOWN_PREFIXES = frozenset({
    "FAULT",       # fault/retry counter instants (common/counters.py)
    "AUTOTUNE",    # tuning-session lifecycle (autotune/driver.py)
    "OVERLAP",     # streamed bucket collectives (docs/overlap.md)
    "SERVE",       # generation-engine events (docs/serving.md)
    "STALL",       # StallInspector instants (monitor/stall.py)
    "METRIC",      # TimelineSink registry mirrors (monitor/sinks.py)
    "PROFILE",     # hvd.profile_window brackets (monitor/profile.py)
    "CYCLE_START",  # HOROVOD_TIMELINE_MARK_CYCLES (reference parity)
    "CKPT",        # async checkpoint lifecycle (docs/checkpoint.md)
    "PP",          # pipeline sends + schedule slots (docs/pipeline.md)
    "MOE",         # expert dispatch/combine exchanges (docs/moe.md)
    "STRAGGLER",   # skew / link-health diagnoses (monitor/straggler.py)
    "FLIGHT",      # flight-recorder marks (monitor/flight.py)
    "RESILIENCE",  # supervisor policy actions (resilience/supervisor.py)
    "COMPILE",     # executable-cache lower/compile/hit (docs/compile.md)
})

#: The ``jax.named_scope`` vocabulary of the compiled step: each name is
#: opened at one place in ``parallel/``, ``ops/`` or a model and lands in the
#: ``op_name`` of every HLO op traced under it, which the device trace
#: carries (docs/observability.md "Scopes in the device trace"). Scopes
#: nest; JAX adds the direction (``jvp(`` / ``transpose(``) itself. The
#: Pallas kernels' ``name=`` are ``hvd_<kernel>`` beside them in ``ops/``.
DEVICE_SCOPES = (
    "hvd.grad",              # parallel/tape.py: the user's loss, fwd + bwd
    "hvd.lm_head_loss",      # ops/softmax_xent.py: head matmul + xent
    "hvd.flash_attention",   # ops/flash_attention.py: kernels + layout
    "hvd.flash_window",      # ops/flash_attention.py: a windowed call, inside
    "hvd.flash_block_diffusion",  # ops/flash_attention.py: a call under
    #                          the block-diffusion mask, inside
    "hvd.block_diffusion_noise",  # ops/block_diffusion.py: a step's noise
    "hvd.layer_norm",        # ops/layer_norm.py: fused residual + LN
    "hvd.sparse_attention",  # ops/sparse_attention.py: kernels + layout
    "hvd.sparse_indexer",    # ops/sparse_attention.py: scores + selection
    "hvd.moe_ffn",           # moe/layer.py: dropless router..combine
    "hvd.moe_route",         # moe/layer.py: a router run apart from its
    #                          experts' walk (moe_route), ahead of attention
    "hvd.shared_expert",     # models/sparse_moe_decoder.py: beside moe_ffn
    "hvd.ssm",               # models/sambay.py, hybrid_mamba_moe.py: a
    #                          state-space mixer
    "hvd.selective_scan",    # ops/selective_scan.py: kernels + their layout
    "hvd.ssd_scan",          # ops/ssd_scan.py: Mamba-2's chunked scan
    "hvd.moe_latent",        # models/hybrid_mamba_moe.py: the projections
    #                          into and out of the experts' latent
    "hvd.gmu",               # models/sambay.py: a gated memory unit
    "hvd.diff_attention",    # models/sambay.py: around the flash call
    "hvd.norm",              # models/: every norm outside ops/layer_norm.py
    "hvd.rotary",            # models/sparse_moe_decoder.py: rope
    "hvd.attn_proj",         # models/: q / k / v / gate / output matmuls
    "hvd.mlp",               # models/: a block's dense MLP
    "hvd.embed",             # models/: token (+ position) lookup
    "hvd.router_bias_update",  # moe/layer.py: the balancing bias, a step
    "hvd.allreduce_grads",   # parallel/optimizer.py, tape.py: grad exchange
    "hvd.bucket_pack",       # ops/fusion.py: leaves -> flat bucket
    "hvd.bucket_allreduce",  # ops/fusion.py: the per-bucket wire op
    "hvd.bucket_unpack",     # ops/fusion.py: flat bucket -> leaves
    "hvd.optimizer_update",  # parallel/optimizer.py: the wrapped optax tx
)


def event_prefix(name: str) -> str:
    """The vocabulary prefix of an event name (the part before the
    first colon; colon-free names are their own prefix)."""
    return name.split(":", 1)[0] if ":" in name else name


class SpanImbalanceError(AssertionError):
    """A tid's B/E events do not balance (or depth went negative)."""


class UnknownSpanPrefixError(AssertionError):
    """``strict=True``: an event's prefix is not in the checked
    vocabulary table (:data:`KNOWN_PREFIXES`)."""


@dataclass
class SpanAudit:
    """Result of :func:`audit_spans`."""

    #: spans fully closed, per tid
    spans_per_tid: Dict[str, int] = field(default_factory=dict)
    #: final (unclosed) depth per tid — all zero when balanced
    open_depth: Dict[str, int] = field(default_factory=dict)
    #: summed span duration (µs) per activity name
    duration_us: Dict[str, float] = field(default_factory=dict)
    #: span count per activity name
    count: Dict[str, int] = field(default_factory=dict)
    #: instant (ph:"i") events seen, per name
    instants: Dict[str, int] = field(default_factory=dict)

    @property
    def balanced(self) -> bool:
        return not any(self.open_depth.values())

    @property
    def total_spans(self) -> int:
        return sum(self.spans_per_tid.values())

    def by_phase(self) -> Dict[str, float]:
        """Duration (µs) grouped by the ``PREFIX:`` before the first
        colon (``OVERLAP``, ``SERVE``, ``PROFILE``, ...)."""
        out: Dict[str, float] = {}
        for name, us in self.duration_us.items():
            phase = name.split(":", 1)[0] if ":" in name else name
            out[phase] = out.get(phase, 0.0) + us
        return out


def load_events(source: Union[str, list]) -> list:
    """Timeline events from a path or an already-loaded list."""
    if isinstance(source, str):
        with open(source) as f:
            return json.load(f)
    return list(source)


def audit_spans(source: Union[str, list], prefix: Optional[str] = None,
                require_balanced: bool = True,
                require_spans: bool = False,
                strict: bool = False) -> SpanAudit:
    """Audit B/E balance per tid over a Timeline file (or event list).

    ``prefix`` restricts the audit to events whose name starts with it
    (e.g. ``"OVERLAP"``, ``"SERVE:"``). With ``require_balanced`` (the
    default) raises :class:`SpanImbalanceError` naming the offending tid
    when any depth goes negative or fails to return to zero;
    ``require_spans`` additionally demands at least one matching span
    closed (guards against a filter that silently matched nothing).
    ``strict`` checks EVERY scanned event (before the ``prefix``
    filter) against the vocabulary table, raising
    :class:`UnknownSpanPrefixError` on the first name whose prefix is
    not in :data:`KNOWN_PREFIXES` — the mode framework span tests run
    in, so the vocabulary stays exhaustive.
    """
    events = load_events(source)
    if strict:
        for ev in events:
            name = str(ev.get("name", ""))
            p = event_prefix(name)
            if p not in KNOWN_PREFIXES:
                raise UnknownSpanPrefixError(
                    f"event {name!r} uses unknown prefix {p!r}: not in "
                    f"the checked vocabulary table "
                    f"(monitor/span_audit.KNOWN_PREFIXES — register new "
                    f"event families there and in docs/observability.md)")
    audit = SpanAudit()
    stacks: Dict[str, List[Tuple[str, float]]] = {}
    for ev in events:
        name = str(ev.get("name", ""))
        if prefix is not None and not name.startswith(prefix):
            continue
        tid = str(ev.get("tid", "main"))
        ph = ev.get("ph")
        if ph == "B":
            stacks.setdefault(tid, []).append((name, ev.get("ts", 0.0)))
        elif ph == "E":
            stack = stacks.setdefault(tid, [])
            if not stack:
                raise SpanImbalanceError(
                    f"tid {tid!r}: 'E' for {name!r} with no open 'B' "
                    f"(negative depth)")
            b_name, b_ts = stack.pop()
            audit.spans_per_tid[tid] = audit.spans_per_tid.get(tid, 0) + 1
            audit.duration_us[b_name] = (
                audit.duration_us.get(b_name, 0.0)
                + max(0.0, ev.get("ts", b_ts) - b_ts))
            audit.count[b_name] = audit.count.get(b_name, 0) + 1
        elif ph == "i":
            audit.instants[name] = audit.instants.get(name, 0) + 1
    for tid, stack in stacks.items():
        audit.open_depth[tid] = len(stack)
        if stack and require_balanced:
            raise SpanImbalanceError(
                f"tid {tid!r}: {len(stack)} span(s) never closed "
                f"(first open: {stack[0][0]!r})")
    if require_spans and audit.total_spans == 0:
        raise SpanImbalanceError(
            f"no spans matched prefix {prefix!r} "
            f"({len(events)} events scanned)")
    return audit
