"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into a
``Trace`` of plain tuples; everything else works on that, so the same
arithmetic runs on a small recorded piece kept with the tests
(``Trace.from_json``). Times are seconds on the trace's own clock.

What the planes and lines of a TPU v5e trace hold is written in PERF.md
(section 3, "Reading the trace"): one ``/device:TPU:<n>`` plane per chip
with an ``XLA Ops`` line (one event per HLO op or kernel the core ran, named
by the op's whole HLO text), an ``Async XLA Ops`` line (copies and
collectives in flight beside them, start to done) and an ``XLA Modules``
line (one event per executed program, here one per step);
the benchmark's spans are ``bench:*`` events of the ``/host:CPU`` plane.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

Event = tuple  # (name, start_s, duration_s)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute",
    re.IGNORECASE)


@dataclasses.dataclass
class Trace:
    ops: dict        # device id -> [Event] of the ops line
    async_ops: dict  # device id -> [Event] of the async ops line
    modules: dict    # device id -> [Event] of the modules line
    host: list       # [Event] of the benchmark's spans, prefix stripped
    window: tuple    # (start_s, end_s) of the traced window

    def to_json(self) -> dict:
        return {"window": list(self.window),
                "host": [list(e) for e in self.host],
                **{key: {str(k): [list(e) for e in v]
                         for k, v in getattr(self, key).items()}
                   for key in ("ops", "async_ops", "modules")}}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def lanes(key):
            return {int(k): [tuple(e) for e in v] for k, v in d[key].items()}
        return cls(lanes("ops"), lanes("async_ops"), lanes("modules"),
                   [tuple(e) for e in d["host"]], tuple(d["window"]))

    def cut(self, start: float, end: float) -> "Trace":
        """The events that lie wholly inside [start, end]."""
        def keep(evs):
            return [e for e in evs if e[1] >= start and e[1] + e[2] <= end]
        return Trace({k: keep(v) for k, v in self.ops.items()},
                     {k: keep(v) for k, v in self.async_ops.items()},
                     {k: keep(v) for k, v in self.modules.items()},
                     keep(self.host), (start, end))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, span_prefix: str = "bench:") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lanes = {OPS_LINE: {}, ASYNC_LINE: {}, MODULES_LINE: {}}
    host = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in lanes:
                    lanes[line.name][int(m.group(1))] = sorted(
                        ((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events), key=lambda e: e[1])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name[len(span_prefix):], e.start_ns * 1e-9,
                             e.duration_ns * 1e-9) for e in line.events
                            if e.name.startswith(span_prefix))
    host.sort(key=lambda e: e[1])
    ops = lanes[OPS_LINE]
    every = [e for v in ops.values() for e in v]
    if not every:
        raise ValueError(f"{path}: no operation ran on a device in the "
                         f"traced window")
    start = min(e[1] for e in every)
    end = max(e[1] + e[2] for e in every)
    return Trace(ops, lanes[ASYNC_LINE], lanes[MODULES_LINE], host,
                 (start, end))


def describe_xplane(path: str, top: int = 25) -> str:
    """What a trace holds, for reading one by hand: every plane and line
    with its event count and span, and the heaviest event names of each
    device line."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            out.append(f"  LINE {line.name!r}: {len(evs)} events, "
                       f"{t0 * 1e-9:.6f}..{t1 * 1e-9:.6f} s")
            if plane.name.startswith("/device:") or any(
                    e.name.startswith("bench:") for e in evs):
                by_name: dict = {}
                for e in evs:
                    acc = by_name.setdefault(e.name, [0, 0.0])
                    acc[0] += 1
                    acc[1] += e.duration_ns * 1e-9
                ranked = sorted(by_name.items(), key=lambda kv: -kv[1][1])
                for name, (n, secs) in ranked[:top]:
                    out.append(f"      {secs * 1e3:10.3f} ms {n:6d}x "
                               f"{name[:110]}")
    return "\n".join(out)


# -- interval arithmetic ----------------------------------------------------


def union(intervals) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def measure(merged) -> float:
    return sum(b - a for a, b in merged)


def subtract(merged_a, merged_b) -> list:
    """The part of ``merged_a`` that ``merged_b`` does not cover."""
    out, j = [], 0
    for a, b in merged_a:
        cur = a
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < b:
            if merged_b[k][0] > cur:
                out.append((cur, merged_b[k][0]))
            cur = max(cur, merged_b[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def clip(intervals, start: float, end: float) -> list:
    return [(max(a, start), min(b, end)) for a, b in intervals
            if min(b, end) > max(a, start)]


def _spans(events) -> list:
    return [(s, s + d) for _, s, d in events]


# -- the numbers ------------------------------------------------------------


def busy_seconds(trace: Trace, device: int) -> float:
    """Seconds inside the window in which at least one op ran on
    ``device``: a union, so lanes that mirror each other count once."""
    return measure(clip(union(_spans(trace.ops[device])), *trace.window))


def mean_busy_seconds(trace: Trace) -> float:
    devs = sorted(trace.ops)
    return sum(busy_seconds(trace, d) for d in devs) / len(devs)


def idle_share(trace: Trace, device: int) -> float:
    length = trace.window[1] - trace.window[0]
    return 1.0 - busy_seconds(trace, device) / length


def steps(trace: Trace, device: int) -> list:
    """[(start, end)] of each training step on ``device``: the executed
    programs whose duration is at least half the longest one's, which
    leaves out any small transfer program between them."""
    mods = trace.modules.get(device, [])
    if not mods:
        return []
    longest = max(d for _, _, d in mods)
    return [(s, s + d) for _, s, d in mods if d >= 0.5 * longest]


def per_step(trace: Trace, device: int, events) -> list:
    """For each step, the measure of the union of ``events`` inside it."""
    merged = union(_spans(events))
    return [measure(clip(merged, a, b)) for a, b in steps(trace, device)]


def step_busy_seconds(trace: Trace, device: int) -> list:
    return per_step(trace, device, trace.ops[device])


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def _op_kind(name: str) -> str:
    """The HLO opcode of an event named by its HLO text
    ('%x.1 = f32[8]{0:T(8)} all-reduce-start(f32[8] %y)' ->
    'all-reduce-start'): the first word followed by '(' after a space. An
    operand's name may hold 'all-reduce' too, so the text is not searched
    whole. A name that is no HLO text is its own kind."""
    m = _OPCODE.search(name.split(" = ", 1)[-1]) if " = " in name else None
    return m.group(1) if m else name


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(_op_kind(name)))


def collective_events(trace: Trace, device: int) -> list:
    """The collective ops of ``device``: those the core ran and those in
    flight on the async line."""
    return [e for e in trace.ops[device] + trace.async_ops.get(device, [])
            if is_collective(e[0])]


def collective_seconds(trace: Trace, device: int) -> tuple:
    """Per step: (the time in which a collective ran or was in flight, the
    part of it in which the core ran no other op)."""
    coll = collective_events(trace, device)
    rest = [e for e in trace.ops[device] if not is_collective(e[0])]
    coll_u, rest_u = union(_spans(coll)), union(_spans(rest))
    exposed = subtract(coll_u, rest_u)
    total, alone = [], []
    for a, b in steps(trace, device):
        total.append(measure(clip(coll_u, a, b)))
        alone.append(measure(clip(exposed, a, b)))
    return total, alone


def kernel_seconds(trace: Trace, device: int, pattern: str) -> list:
    """Durations of the events of ``device`` whose name matches."""
    rx = re.compile(pattern)
    return [d for name, _, d in trace.ops[device] if rx.search(name)]


def top_ops(trace: Trace, device: int, k: int = 10) -> list:
    """[[name, seconds]] of the op names that took most device time."""
    by_name: dict = {}
    for name, _, d in trace.ops[device]:
        by_name[name] = by_name.get(name, 0.0) + d
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
    return [[name[:120], secs] for name, secs in ranked]


def idle_gaps(trace: Trace, device: int, k: int = 10) -> list:
    """[[what the host was doing, seconds]] of the longest gaps between
    device ops inside the window. A gap is named after the benchmark span
    that overlaps it longest, ``(no span)`` where none does."""
    gaps = subtract([trace.window], union(_spans(trace.ops[device])))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    out = []
    for a, b in gaps:
        best, best_overlap = "(no span)", 0.0
        for name, s, d in trace.host:
            overlap = min(b, s + d) - max(a, s)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        out.append([best, b - a])
    return out


if __name__ == "__main__":
    import sys

    print(describe_xplane(find_xplane(sys.argv[1])
                          if os.path.isdir(sys.argv[1]) else sys.argv[1]))
