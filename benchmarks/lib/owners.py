"""The device trace read by the OWNERS of each instruction: the join of
``lib/scopes.py``'s pieces with ``horovod_tpu.monitor.hlo_owners``' map of
the compiled step.

``lib/scopes.py`` reads one path an event, and a fusion's is its root's:
LayerNorm fused into the matmul after it, AdamW fused into the unscoped
``add`` of ``optax.apply_updates``, and the copies the compiler placed (no
path at all) read under the wrong name or under none. The compiled
program's text names every instruction inside every fusion;
``hlo_owners.owners`` turns it into ``{instruction: {(owner, direction):
weight}}`` and this file spreads each piece of ``scopes.of(run).innermost``
(pieces that do not overlap) over its instruction's owners, by the
instruction name at the head of the event's text. The reduction is
``ScopedOps``' own: the time inside each traced step, median over the
steps.

``Run`` holds no handle on the session and needs none: the profiler
writes the program it traced into the trace. The ``.xplane.pb`` has a plane
``/host:metadata`` whose event metadata, one an executed module and named
like the module's events (``jit_spmd(<fingerprint>)``), carry the stat ``Hlo
Proto``: the serialized ``HloProto`` of the optimized module, the very
program the traced steps ran. XLA's own printer turns it into the text
``compiled.as_text()`` gives (found on the chip in PR 37: 6.2 MB of proto,
5.0 MB of text in 0.4 s, where building the cell's session again and
loading its step from the compile cache took 10.3 s). That happens in a
``--trace 1`` run only, when the first of the readers asks, after the
window and the reference; its seconds are printed. A checkout whose
program has no ``hlo_owners`` (every commit before PR 37), or a trace
without the proto, reads nothing and raises nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
import statistics
import time

from benchmarks.lib import scopes

try:
    from horovod_tpu.monitor import hlo_owners
except ImportError:            # a program from before the map
    hlo_owners = None

NOT_IN_TEXT_SHOWN = 8          # names listed in the note, heaviest first
METADATA_PLANE, HLO_STAT = "/host:metadata", "Hlo Proto"


def _named(buf) -> str:
    """Field 2 of a message as text: the name of a plane, an event's
    metadata or a stat's."""
    return next((bytes(v).decode("utf-8", "replace")
                 for n, v in scopes.fields(buf) if n == 2), "")


def _entries(plane, number: int):
    """(key, value) of a plane's map field ``number`` (event metadata 4,
    stat metadata 5: lib/scopes.py lists the fields)."""
    for n, entry in scopes.fields(plane):
        if n == number:
            pair = dict(scopes.fields(entry))
            yield pair.get(1), pair.get(2)


def traced_hlo(xplane: str, modules) -> str | None:
    """The text of the optimized module the trace holds under one of the
    names ``modules``; None where it holds none or this jaxlib cannot
    print one."""
    try:
        from jax._src.lib import xla_client
        from_proto = xla_client._xla.HloModule.from_serialized_hlo_module_proto
    except (ImportError, AttributeError):
        return None
    with open(xplane, "rb") as f:
        space = memoryview(f.read())
    for n, plane in scopes.fields(space):
        if n != 1 or _named(plane) != METADATA_PLANE:
            continue
        hlo_stats = {key for key, md in _entries(plane, 5)
                     if _named(md) == HLO_STAT}
        for _, md in _entries(plane, 4):
            if _named(md) not in modules:
                continue
            for number, stat in scopes.fields(md):
                stat = dict(scopes.fields(stat)) if number == 5 else {}
                if stat.get(1) in hlo_stats and 6 in stat:
                    # XStat.bytes_value = 6; HloProto.hlo_module = 1
                    module = dict(scopes.fields(stat[6]))[1]
                    return from_proto(bytes(module)).to_string()
    return None


@dataclasses.dataclass
class Owned:
    """A traced run's busy time by owner, step by step."""
    per_step: list    # a step: {(owner, direction): seconds}
    mixed_s: list     # a step: seconds in instructions of several owners
    missing: dict     # {instruction name not in the text: seconds}
    owners_map: dict  # hlo_owners.owners() of the compiled step
    steps: list       # [(start_s, end_s)] of the traced steps

    def ms(self, accept) -> float | None:
        """Median over the steps of the milliseconds whose (owner,
        direction) ``accept`` takes; None where no step has any."""
        if not any(accept(*key) for step in self.per_step for key in step):
            return None
        return 1e3 * statistics.median(
            sum(s for key, s in step.items() if accept(*key))
            for step in self.per_step)

    def owner_ms(self, owner: str) -> float | None:
        return self.ms(lambda o, _: o == owner)

    def busy_ms(self) -> float:
        return self.ms(lambda *_: True)

    def mixed_ms(self) -> float:
        return 1e3 * statistics.median(self.mixed_s)

    def events_per_step(self, ops, prefix: str, key: tuple) -> float | None:
        """Median count a step of the events of ``ops`` whose instruction's
        name begins ``prefix`` and which ``key`` owns whole; None where no
        step has one."""
        starts = sorted(op[1] for op in ops if self.owners_map.get(
            name := hlo_owners.instruction_name(op[0])) == {key: 1.0}
            and name.startswith(prefix))
        counts = [bisect.bisect_left(starts, b) - bisect.bisect_left(
            starts, a) for a, b in self.steps]
        return statistics.median(counts) if any(counts) else None


def join(scoped: scopes.ScopedOps, owners_map: dict) -> Owned | None:
    """Spread the pieces of ``scoped.innermost`` inside the traced steps
    over the owners of their instructions; None where there are none."""
    steps = sorted(scoped.steps)
    starts = [a for a, _ in steps]
    per_step = [{} for _ in steps]
    mixed_s = [0.0] * len(steps)
    missing: dict = {}
    nowhere = {(hlo_owners.UNOWNED, hlo_owners.FORWARD): 1.0}
    for a, b, op in scoped.innermost:
        at = bisect.bisect_right(starts, a) - 1
        if at < 0 or a >= steps[at][1]:
            continue                       # between two steps
        seconds = min(b, steps[at][1]) - a
        name = hlo_owners.instruction_name(op[0])
        shares = owners_map.get(name)
        if shares is None:
            missing[name] = missing.get(name, 0.0) + seconds
            shares = nowhere
        elif hlo_owners.mixed(shares):
            mixed_s[at] += seconds
        totals = per_step[at]
        for key, weight in shares.items():
            totals[key] = totals.get(key, 0.0) + weight * seconds
    if not any(per_step):
        return None                        # nothing ran inside a step
    return Owned(per_step, mixed_s, missing, owners_map, steps)


def of(run) -> Owned | None:
    """The owned busy time of a traced run, made once and kept on the run;
    None where the run was not traced, its trace or its cell cannot be
    found, or the program has no ``hlo_owners``."""
    if not hasattr(run, "owned"):
        run.owned = _read(run)
    return run.owned


def _read(run) -> Owned | None:
    scoped = scopes.of(run) if hlo_owners is not None else None
    if scoped is None:
        return None
    t0 = time.perf_counter()
    device = min(run.trace.ops)
    modules = {name for name, start, seconds in run.trace.modules[device]
               if (start, start + seconds) in scoped.steps}
    text = traced_hlo(scopes.newest_xplane(), modules)
    if text is None:
        return None
    t1 = time.perf_counter()
    owners_map = hlo_owners.owners(text)
    owned = join(scoped, owners_map)
    t2 = time.perf_counter()
    if owned is None:
        return None
    busy_s = sum(sum(step.values()) for step in owned.per_step)
    lost_s = sum(owned.missing.values())
    heaviest = sorted(owned.missing.items(), key=lambda kv: -kv[1])
    run.note(f"lib/owners.py: {sorted(modules)} printed from the trace's "
             f"own proto in {t1 - t0:.2f} s, {len(text) / 1e6:.1f} MB of "
             f"text with "
             f"{len(owners_map)} instructions parsed and joined in "
             f"{t2 - t1:.2f} s; {100 * (1 - lost_s / busy_s):.3f}% of the "
             f"traced busy time in instructions the text names"
             + (f", not in it ({len(heaviest)}): " + ", ".join(
                 f"{name} {1e3 * s / len(owned.per_step):.3f} ms/step"
                 for name, s in heaviest[:NOT_IN_TEXT_SHOWN])
                if heaviest else ""))
    partition = {key: owned.ms(lambda o, d, key=key: (o, d) == key)
                 for key in sorted({k for step in owned.per_step
                                    for k in step})}
    total = sum(partition.values())
    run.note("lib/owners.py: " + ", ".join(
        f"{o}.{d} {ms:.3f}" for (o, d), ms in partition.items())
        + f" ms; sum {total:.3f} against busy {owned.busy_ms():.3f} ms "
        f"({100 * (total / owned.busy_ms() - 1):+.3f}%)")
    return owned
