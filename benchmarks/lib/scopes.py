"""The device trace read by the names the program gives its work.

Every HLO op traced under a ``jax.named_scope`` carries the scope's path in
its ``op_name`` (``jit(spmd)/shard_map/hvd.grad/transpose(hvd.grad)/
jvp(GPT)/h3/attn/hvd.flash_attention/...``); the vocabulary is
``horovod_tpu.monitor.span_audit.DEVICE_SCOPES``. The TPU profiler keeps
that path as the ``tf_op`` stat of each op's *event metadata* in the
``.xplane.pb``. ``jax.profiler.ProfileData`` shows an event's own stats
only, and ``lib/trace.py`` keeps ``(name, start, duration)``, so this file
reads the ``.xplane.pb`` itself, with a protobuf wire reader of its own
(no generated code; the fields are those of tsl's ``xplane.proto``).
PERF.md, section 3, "Reading the trace", says what was found where.

The rules of the reduction:

* an event is *under* a scope if the scope's name is anywhere in its path
  (scopes nest);
* its direction is backward iff the path holds ``transpose(``;
* an event nested in another on the line counts once, as the innermost;
* a number is the union of intervals inside each step (steps as
  ``lib/trace.py steps()`` finds them), median over the traced steps;
* a scope never seen gives ``None`` (a program without the scopes, as
  every commit before PR 24 is, reports nothing and raises nothing);
* the classes partition the events by their *outermost* ``hvd.*`` name:
  ``hvd.grad`` splits into forward and backward, events with none are
  ``unscoped``.

A program that runs no TPU leaves nothing to read: every entry point
returns ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
import statistics
import struct
import time

from benchmarks.lib import manifest as mf, trace as tr

HVD_NAME = re.compile(r"hvd\.[a-z0-9_]+")
BACKWARD = "transpose("
UNSCOPED = "unscoped"
PATH_STAT = "tf_op"

Op = tuple  # (name, start_s, duration_s, path)


# -- the .xplane.pb, field by field -------------------------------------------
# XSpace.planes=1; XPlane: name=2 lines=3 event_metadata=4 stat_metadata=5;
# XLine: name=2 timestamp_ns=3 events=4; XEvent: metadata_id=1 offset_ps=2
# duration_ps=3; XEventMetadata: name=2 stats=5; XStatMetadata: name=2;
# XStat: metadata_id=1 str_value=5 ref_value=7; a map entry: key=1 value=2.


def _varint(buf, pos: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf):
    """(field number, value) of each field of one encoded message: an int
    for a varint or a fixed-width field, a memoryview for a
    length-delimited one."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 1:
            value, pos = struct.unpack_from("<Q", buf, pos)[0], pos + 8
        elif wire == 5:
            value, pos = struct.unpack_from("<I", buf, pos)[0], pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _first(buf, number: int):
    for n, v in fields(buf):
        if n == number:
            return v
    return None


def _map_entry(buf) -> tuple:
    key = value = None
    for n, v in fields(buf):
        if n == 1:
            key = v
        elif n == 2:
            value = v
    return key, value


def _plane_ops(plane) -> list:
    """[Op] of the plane's ``XLA Ops`` line, sorted by start."""
    stat_names, metadata, lines = {}, {}, []
    for n, v in fields(plane):
        if n == 5:
            key, value = _map_entry(v)
            stat_names[key] = _text(_first(value, 2) or b"")
        elif n == 4:
            key, value = _map_entry(v)
            metadata[key] = value
        elif n == 3:
            lines.append(v)
    path_ids = {k for k, name in stat_names.items() if name == PATH_STAT}

    def describe(md) -> tuple:
        name, path = "", ""
        for n, v in fields(md):
            if n == 2:
                name = _text(v)
            elif n == 5:
                stat = dict(fields(v))
                if stat.get(1) in path_ids:
                    path = (_text(stat[5]) if 5 in stat
                            else stat_names.get(stat.get(7), ""))
        return name, path

    described = {k: describe(md) for k, md in metadata.items()}
    ops = []
    for line in lines:
        if _text(_first(line, 2) or b"") != tr.OPS_LINE:
            continue
        origin_ps = (_first(line, 3) or 0) * 1000
        for n, v in fields(line):
            if n != 4:
                continue
            ev = dict(fields(v))
            name, path = described.get(ev.get(1), ("", ""))
            ops.append((name, (origin_ps + ev.get(2, 0)) * 1e-12,
                        ev.get(3, 0) * 1e-12, path))
    ops.sort(key=lambda e: e[1])
    return ops


def load(path: str) -> list:
    """[Op] of the first TPU device of an ``.xplane.pb``; empty where the
    file has no such plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for n, plane in fields(space):
        if n == 1:
            m = tr.DEVICE_PLANE.match(_text(_first(plane, 2) or b""))
            if m:
                planes[int(m.group(1))] = plane
    return _plane_ops(planes[min(planes)]) if planes else []


def newest_xplane(root: str = mf.ROOT):
    """The newest ``.bench_trace/<cell>/plugins/profile/<t>/*.xplane.pb``
    of the checkout (the harness writes one a traced run), or None."""
    found = glob.glob(os.path.join(root, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


# -- what the readers are handed ------------------------------------------------


@dataclasses.dataclass
class ScopedOps:
    ops: list     # [Op] of the first device, sorted by start
    steps: list   # [(start_s, end_s)] of the traced steps on that device

    @classmethod
    def from_json(cls, d: dict, device: int = 0) -> "ScopedOps":
        """From a recorded piece: a ``Trace.to_json()`` with one more key,
        ``paths`` (device -> the path of each op, in the ops' order)."""
        trace = tr.Trace.from_json(d)
        paths = d["paths"][str(device)]
        ops = [(*e, p) for e, p in zip(trace.ops[device], paths,
                                       strict=True)]
        return cls(ops, tr.steps(trace, device))

    @functools.cached_property
    def innermost(self) -> list:
        """[(start, end, op)]: the line cut so that no two pieces
        overlap, each piece given to the innermost op that covers it."""
        pieces, stack = [], []   # stack of [op, end, covered_to]

        def close(until: float) -> None:
            while stack and stack[-1][1] <= until:
                op, end, at = stack.pop()
                if end > at:
                    pieces.append((at, end, op))
                if stack:
                    stack[-1][2] = max(stack[-1][2], end)

        for op in sorted(self.ops, key=lambda e: (e[1], -e[2])):
            start, end = op[1], op[1] + op[2]
            close(start)
            if stack:
                if start > stack[-1][2]:
                    pieces.append((stack[-1][2], start, stack[-1][0]))
                stack[-1][2] = max(stack[-1][2], start)
                end = min(end, stack[-1][1])
            stack.append([op, end, start])
        close(float("inf"))
        pieces.sort(key=lambda p: p[0])
        return pieces

    def _median_ms(self, spans) -> float | None:
        """Median over the steps of the milliseconds of ``spans`` inside
        each; None where there is nothing to measure."""
        if not spans or not self.steps:
            return None
        merged = tr.union(spans)
        return statistics.median(tr.measure(tr.clip(merged, a, b))
                                 for a, b in self.steps) * 1e3

    def scope_ms(self, *scopes: str, direction: str | None = None,
                 keep=None) -> float | None:
        """Median over the steps of the milliseconds under any of
        ``scopes``; ``direction`` is ``forward``, ``backward`` or both
        (None); ``keep`` may refuse single ops."""
        def accept(op) -> bool:
            path = op[3]
            if not any(scope in path for scope in scopes):
                return False
            if direction and (BACKWARD in path) != (direction == "backward"):
                return False
            return keep is None or keep(op)
        return self._median_ms([(a, b) for a, b, op in self.innermost
                                if accept(op)])

    def classes_ms(self) -> dict | None:
        """{class: median milliseconds per step} of the partition by the
        outermost ``hvd.*`` name; None where no op carries one."""
        per_class: dict = {}
        for a, b, op in self.innermost:
            per_class.setdefault(outermost_class(op[3]), []).append((a, b))
        if set(per_class) <= {UNSCOPED} or not self.steps:
            return None
        return {name: self._median_ms(spans)
                for name, spans in per_class.items()}

    def calls_per_step(self, word: str) -> float:
        """Median count per step of the ops with ``word`` in their name or
        path (a Pallas kernel's ``name=`` lands in one or the other)."""
        counts = [sum(1 for op in self.ops
                      if a <= op[1] < b and mentions(op, word))
                  for a, b in self.steps]
        return statistics.median(counts) if counts else 0


def mentions(op: Op, word: str) -> bool:
    """Whether ``word`` is in the op's HLO text or in its path."""
    return word in op[0] or word in op[3]


def outermost_class(path: str) -> str:
    m = HVD_NAME.search(path)
    if m is None:
        return UNSCOPED
    if m.group(0) == "hvd.grad":
        return ("hvd.grad.backward" if BACKWARD in path
                else "hvd.grad.forward")
    return m.group(0)


def of(run) -> ScopedOps | None:
    """The scoped ops of a traced run, read once and kept on the run; None
    where the run was not traced or its trace cannot be found."""
    if not hasattr(run, "scoped_ops"):
        run.scoped_ops = _read(run)
    return run.scoped_ops


def _read(run) -> ScopedOps | None:
    path = newest_xplane() if run.trace is not None else None
    if path is None:
        return None
    device = min(run.trace.ops)
    t0 = time.perf_counter()
    ops = load(path)
    if len(ops) != len(run.trace.ops[device]):
        return None    # another run's file
    run.note(f"lib/scopes.py: {os.path.getsize(path) / 1e6:.1f} MB of "
             f".xplane.pb, {len(ops)} ops of device {device} read in "
             f"{time.perf_counter() - t0:.2f} s, "
             f"{sum(1 for op in ops if op[3])} with a path")
    return ScopedOps(ops, tr.steps(run.trace, device))


def describe(scoped: ScopedOps, top: int = 40) -> str:
    """For reading a trace by hand: the classes, and the heaviest path
    prefixes with their time per step."""
    out = [f"{len(scoped.ops)} ops, {len(scoped.steps)} steps"]
    for name, ms in sorted((scoped.classes_ms() or {}).items()):
        out.append(f"  class {name:24s} {ms:9.3f} ms/step")
    by_prefix: dict = {}
    for a, b, op in scoped.innermost:
        found = HVD_NAME.findall(op[3])
        key = "/".join(dict.fromkeys(found)) or UNSCOPED
        if BACKWARD in op[3]:
            key += " (backward)"
        by_prefix[key] = by_prefix.get(key, 0.0) + (b - a)
    steps = max(1, len(scoped.steps))
    for key, secs in sorted(by_prefix.items(), key=lambda kv: -kv[1])[:top]:
        out.append(f"  {secs * 1e3 / steps:9.3f} ms/step  {key}")
    return "\n".join(out)


if __name__ == "__main__":   # python3 -m benchmarks.lib.scopes [file]
    import sys

    found = sys.argv[1] if len(sys.argv) > 1 else newest_xplane()
    whole = tr.load_xplane(found)
    print(describe(ScopedOps(load(found),
                             tr.steps(whole, min(whole.ops)))))
