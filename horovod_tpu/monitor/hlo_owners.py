"""Who owns each instruction of a compiled program: the join the device
trace lacks (docs/observability.md, "Scopes in the device trace").

A device event carries ONE path, its instruction's ``op_name``, and for a
fusion that is the path of the fusion's root: one instruction of many. The
compiled program's text (``compiled.as_text()``) holds every fused
computation with the ``op_name`` of each instruction inside it and names
the operands of every copy. :func:`owners` reads that text and gives every
instruction its owners; :func:`by_owner` reduces ``(event text, seconds)``
pairs of a trace with the map. Pure text in, dicts out: nothing here
imports JAX.

An instruction's **owner** is the innermost ``hvd.*`` name of its path
other than ``hvd.grad`` (``span_audit.DEVICE_SCOPES``); ``hvd.grad`` itself
where the path holds no other, ``unowned`` where it holds none. Its
**direction** is ``remat`` where the path holds ``rematted_computation``
(the forward that ``jax.checkpoint`` / ``nn.remat`` runs again inside the
backward pass), else ``backward`` iff it holds ``transpose(``, else
``forward``. The rules, in the order they are tried:

1. a **fusion** (any instruction that ``calls=`` a computation) is owned by
   the instructions of that computation, nested calls followed and a
   reducer's ``to_apply=`` left out; parameters, constants, broadcasts of
   constants, tuples, ``get-tuple-element`` and bitcasts weigh nothing. If
   one of them is a ``dot``, a ``convolution`` or a custom call the whole
   weight goes to those: a matmul fusion's time is the matmul's, its
   prologue and epilogue ride on it. Otherwise each inner instruction
   weighs the bytes of its result. That is a convention, not a
   measurement: ``mixed`` says which instructions rest on it;
2. a plain instruction with an ``op_name``: its owner, weight 1;
3. an instruction with no ``op_name`` (``copy-start`` / ``copy-done``,
   ``slice-start`` / ``slice-done``, bitcasts the compiler placed), or
   with one that is no path of the program (a parameter's is its
   argument's name; the TPU compiler calls the grouped matmuls it makes of
   ``lax.ragged_dot`` ``ragged-dot-none``): the owners of the producer of
   its first operand, followed through other path-less instructions of its
   computation, else of its first user; ``unowned`` when neither has one.

Instructions inside ``while`` bodies and conditionals are instructions
like any other: the trace has an event for each.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, NamedTuple, Tuple

GRAD = "hvd.grad"
UNOWNED = "unowned"
FORWARD, BACKWARD, REMAT = "forward", "backward", "remat"
BACKWARD_MARK = "transpose("
#: The name ``jax.checkpoint`` gives the forward it runs again (read off
#: the lowered text of the ``nn.remat`` blocks: ``.../checkpoint/
#: rematted_computation/h1/...``).
REMAT_MARK = "rematted_computation"

Key = Tuple[str, str]            # (owner, direction)
Shares = Dict[Key, float]        # weights of one instruction, summing to 1

_HVD_NAME = re.compile(r"hvd\.[a-z0-9_]+")
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"([\w\-]+)\(")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_COMMENT = re.compile(r"/\*.*?\*/")
_ARRAY = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_BITS = re.compile(r"[a-z]+?(\d+)")
# Left out of a fusion's weighing: they move no data of their own.
_WEIGHTLESS = frozenset({"parameter", "constant", "tuple",
                         "get-tuple-element", "bitcast"})
# A fusion that holds one of these is that instruction's, whole.
_HEAVY = frozenset({"dot", "convolution", "custom-call"})


class Instruction(NamedTuple):
    name: str
    opcode: str
    shape: str        # the result's, as the text has it
    operands: tuple   # names, in order
    path: str         # the ``op_name``, "" where the text has none
    calls: str        # the computation a fusion or wrapper runs, else ""


def instruction_name(text: str) -> str:
    """The name at the head of an instruction's text, as the compiled text
    and a device event both begin: ``%fusion.7 = f32[8] fusion(...)`` ->
    ``fusion.7``."""
    m = _HEAD.match(text)
    return m.group(1) if m else ""


def key_of(path: str) -> Key:
    """(owner, direction) of one ``op_name``."""
    inner = [n for n in _HVD_NAME.findall(path) if n != GRAD]
    owner = inner[-1] if inner else (GRAD if GRAD in path else UNOWNED)
    if REMAT_MARK in path:
        return owner, REMAT
    return owner, BACKWARD if BACKWARD_MARK in path else FORWARD


def result_bytes(shape: str) -> float:
    """Bytes of a result: elements x dtype width, a tuple's parts summed
    (``token[]`` and opaque parts count nothing)."""
    total = 0.0
    for dtype, dims in _ARRAY.findall(shape):
        bits = _BITS.match(dtype)
        width = int(bits.group(1)) / 8 if bits else (
            1 if dtype == "pred" else 0)
        elements = 1
        for d in filter(None, dims.split(",")):
            elements *= int(d)
        total += elements * width
    return total


def _balanced(text: str, start: int) -> int:
    """Index just past the bracket that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _operand_names(inside: str) -> tuple:
    """The names of ``a, f32[8]{0} %b, %c``: the last word of each part
    between top-level commas."""
    names, depth, start = [], 0, 0
    inside = _COMMENT.sub("", inside)      # ``/*index=5*/%x``
    for i, c in enumerate(inside + ","):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == "," and depth == 0:
            part = inside[start:i].split()
            if part:
                names.append(part[-1].lstrip("%"))
            start = i + 1
    return tuple(names)


def _instruction(name: str, rest: str) -> Instruction | None:
    """``rest`` is what follows ``name =``: shape, opcode(operands),
    attributes."""
    if rest.startswith("("):
        end = _balanced(rest, 0)
    else:
        end = rest.find(" ")
    if end <= 0:
        return None
    shape, tail = rest[:end], rest[end:].lstrip()
    m = _OPCODE.match(tail)
    if m is None:
        return None
    opcode = m.group(1)
    close = _balanced(tail, m.end() - 1)
    attributes = tail[close:]
    # Not every op_name is a path of the program: a parameter's is its
    # argument's name (``p['h0']['ln1']``) and the compiler makes some up
    # (``ragged-dot-none``). A path starts at the jitted function and has
    # a ``/``; the others are owned as what they read or feed is.
    path = None if opcode == "parameter" else _OP_NAME.search(attributes)
    calls = _CALLS.search(attributes)
    return Instruction(name, opcode, shape,
                       _operand_names(tail[m.end():close - 1]),
                       path.group(1) if path and "/" in path.group(1) else "",
                       calls.group(1) if calls else "")


def parse(hlo_text: str) -> Dict[str, List[Instruction]]:
    """{computation: its instructions in the text's order}."""
    computations: Dict[str, List[Instruction]] = {}
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
        elif line.startswith("}"):
            current = None
        else:
            m = _INSTRUCTION.match(line)
            inst = m and _instruction(m.group(1), m.group(2))
            if inst:
                current.append(inst)
    return computations


class _Program:
    """The parsed text with what the three rules look up."""

    def __init__(self, hlo_text: str):
        self.computations = parse(hlo_text)
        self.by_name: Dict[str, Instruction] = {}
        self.home: Dict[str, str] = {}
        self.first_user: Dict[str, Instruction] = {}
        for comp, instructions in self.computations.items():
            for inst in instructions:
                self.by_name[inst.name] = inst
                self.home[inst.name] = comp
            for inst in instructions:
                for operand in inst.operands:
                    if self.home.get(operand) == comp:
                        self.first_user.setdefault(operand, inst)
        self._own: Dict[str, Shares | None] = {}

    def of(self, inst: Instruction) -> Shares:
        return self._resolved(inst) or {(UNOWNED, FORWARD): 1.0}

    def _resolved(self, inst: Instruction) -> Shares | None:
        return self.own(inst) or self._inherited(inst)

    def own(self, inst: Instruction) -> Shares | None:
        """Rules 1 and 2: what the instruction's own text says, None where
        it says nothing."""
        if inst.name not in self._own:
            self._own[inst.name] = None      # a call that calls itself
            found = self._fusion(inst) if inst.calls else None
            if not found and inst.path:
                found = {key_of(inst.path): 1.0}
            self._own[inst.name] = found
        return self._own[inst.name]

    def _weighing(self, computation: str, seen: set) -> list:
        """The instructions of a fused computation that weigh, nested
        calls followed."""
        out = []
        if computation in seen:
            return out
        seen.add(computation)
        for inst in self.computations.get(computation, ()):
            if inst.calls:
                out.extend(self._weighing(inst.calls, seen))
            elif inst.opcode in _WEIGHTLESS or (
                    inst.opcode == "broadcast" and all(
                        self.by_name[o].opcode == "constant"
                        for o in inst.operands if o in self.by_name)):
                continue
            else:
                out.append(inst)
        return out

    def _fusion(self, inst: Instruction) -> Shares | None:
        """Rule 1. An inner instruction that names no owner and inherits
        none inside its computation goes by the fusion's own path (its
        root's), where it has one."""
        inner = self._weighing(inst.calls, set())
        heavy = [i for i in inner if i.opcode in _HEAVY]
        fallback = {key_of(inst.path): 1.0} if inst.path else None
        shares: Shares = {}
        for i in heavy or inner:
            weight = result_bytes(i.shape)
            for key, part in (self._resolved(i) or fallback or {}).items():
                shares[key] = shares.get(key, 0.0) + part * weight
        total = sum(shares.values())
        return {k: v / total for k, v in shares.items()} if total else None

    def _inherited(self, inst: Instruction) -> Shares | None:
        """Rule 3: up the first operands, then down the first users, inside
        the instruction's computation, through instructions whose own text
        names no owner."""
        comp = self.home[inst.name]
        for step in (self._producer, self.first_user.get):
            cur, seen = step(inst.name), {inst.name}
            while cur is not None and cur.name not in seen \
                    and self.home[cur.name] == comp:
                if self.own(cur):
                    return self.own(cur)
                seen.add(cur.name)
                cur = step(cur.name)
        return None

    def _producer(self, name: str) -> Instruction | None:
        operands = self.by_name[name].operands
        return self.by_name.get(operands[0]) if operands else None


def owners(hlo_text: str) -> Dict[str, Shares]:
    """{instruction name: {(owner, direction): weight}} of every
    instruction of ``compiled.as_text()``, each one's weights summing to 1
    (the module's docstring has the rules)."""
    program = _Program(hlo_text)
    return {name: dict(program.of(inst))
            for name, inst in program.by_name.items()}


def mixed(shares: Shares) -> bool:
    """Whether the instruction's weights rest on the bytes convention: a
    fusion whose inner instructions have more than one owner."""
    return len({owner for owner, _ in shares}) > 1


def by_owner(events: Iterable[Tuple[str, float]],
             owners_map: Dict[str, Shares]) -> Tuple[Dict[Key, float],
                                                     Dict[str, float]]:
    """Reduce ``(instruction text, seconds)`` pairs (a device event's name
    and duration, from ``jax.profiler.ProfileData``) by owner: ``({(owner,
    direction): seconds}, {instruction name not in the map: seconds})``."""
    totals: Dict[Key, float] = {}
    missing: Dict[str, float] = {}
    for text, seconds in events:
        name = instruction_name(text)
        shares = owners_map.get(name)
        if shares is None:
            missing[name] = missing.get(name, 0.0) + seconds
            continue
        for key, weight in shares.items():
            totals[key] = totals.get(key, 0.0) + weight * seconds
    return totals, missing
