"""The device trace read by the owners of each instruction
(benchmarks/lib/owners.py): the join on hand-made pieces and on pieces
recorded on the chip with hand-made owners' maps, the ten readers, and the
route the compiled text takes to them, at the tiny size on CPU devices."""

import gzip
import json
import os
import re
import types

import jax
import pytest

import horovod_tpu as hvd
from benchmarks.lib import manifest as mf, owners, scopes, trace as tr
from horovod_tpu.monitor import hlo_owners as ho
from horovod_tpu.monitor.span_audit import DEVICE_SCOPES

import bench_tiny as tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("proj.ms", "mlp.ms", "norm.ms", "rotary.ms", "grad.unowned_ms",
           "grad.remat_ms", "optimizer.ms", "step.unowned_pct",
           "step.mixed_pct", "moe.tiles_per_step")
# ... and the cells each is declared for (ISSUE 37's table)
GPT2 = ["gpt2-medium.train-1chip", "gpt2-small.train-1chip",
        "gpt2-medium.train-4chip"]
SPARSE, TRINITY = ("keye-vl2-30b-a3b.train-16k-1chip",
                   "trinity-mini.train-8k-1chip")
CELLS = {"mlp.ms": GPT2 + [TRINITY], "rotary.ms": [SPARSE, TRINITY],
         "grad.remat_ms": [SPARSE, TRINITY],
         "moe.tiles_per_step": [SPARSE, TRINITY]}

F, B, R = ho.FORWARD, ho.BACKWARD, ho.REMAT
MAP = {
    "qkv_fusion": {("hvd.attn_proj", F): 1.0},
    "norm_rotary_fusion": {("hvd.norm", F): 0.5, ("hvd.rotary", F): 0.5},
    "mlp_fusion": {("hvd.mlp", B): 1.0},
    "remat_norm": {("hvd.norm", R): 1.0},
    "residual": {(ho.GRAD, B): 1.0},
    "adamw_fusion": {("hvd.optimizer_update", F): 0.75,
                     (ho.UNOWNED, F): 0.25},
    "copy-done.7": {(ho.UNOWNED, F): 1.0},
    "while.1": {("hvd.moe_ffn", B): 1.0},
    "ragged-dot-metadata.5": {("hvd.moe_ffn", B): 1.0},
    "ragged-dot-metadata.4": {("hvd.moe_ffn", R): 1.0},
}
# (instruction, start, seconds). Two steps of 20 s; the second walks one
# tile more and its AdamW is longer. A stranger the text does not name, an
# event between the steps, and a while whose body's events lie inside it.
EVENTS = [("qkv_fusion", 0.0, 2.0), ("norm_rotary_fusion", 2.0, 2.0),
          ("remat_norm", 4.0, 1.0), ("mlp_fusion", 5.0, 3.0),
          ("residual", 8.0, 1.0), ("while.1", 9.0, 4.0),
          ("ragged-dot-metadata.5", 9.5, 0.5),
          ("ragged-dot-metadata.4", 10.0, 0.5),
          ("ragged-dot-metadata.5", 11.0, 0.5),
          ("adamw_fusion", 13.0, 4.0), ("copy-done.7", 17.0, 1.0),
          ("stranger.3", 18.0, 1.0),
          ("copy-done.7", 20.5, 1.0),                 # between the steps
          ("qkv_fusion", 22.0, 2.0), ("norm_rotary_fusion", 24.0, 2.0),
          ("remat_norm", 26.0, 1.0), ("mlp_fusion", 27.0, 3.0),
          ("residual", 30.0, 1.0), ("while.1", 31.0, 4.0),
          ("ragged-dot-metadata.5", 31.5, 0.5),
          ("ragged-dot-metadata.5", 32.5, 0.5),
          ("ragged-dot-metadata.5", 33.5, 0.5),
          ("adamw_fusion", 35.0, 6.0), ("copy-done.7", 41.0, 1.0)]
STEPS = [(0.0, 20.0), (22.0, 42.0)]


def _scoped(events=EVENTS, steps=STEPS):
    return scopes.ScopedOps(
        [(f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", start, seconds,
          "") for name, start, seconds in events], steps)


def _run(trace=None):
    notes = []
    return types.SimpleNamespace(trace=trace, chips=1, note=notes.append,
                                 notes=notes)


def _owned_run(owned):
    run = _run()
    run.owned = owned
    run.scoped_ops = _scoped()
    return run


# -- the join -----------------------------------------------------------------


def test_join_spreads_each_piece_over_its_owners():
    owned = owners.join(_scoped(), MAP)
    assert owned.per_step[0] == pytest.approx({
        ("hvd.attn_proj", F): 2.0, ("hvd.norm", F): 1.0,
        ("hvd.rotary", F): 1.0, ("hvd.norm", R): 1.0, ("hvd.mlp", B): 3.0,
        (ho.GRAD, B): 1.0,
        # the while's own time is what its body's events leave: 4 - 1.5
        ("hvd.moe_ffn", B): 2.5 + 1.0, ("hvd.moe_ffn", R): 0.5,
        ("hvd.optimizer_update", F): 3.0,
        (ho.UNOWNED, F): 1.0 + 1.0 + 1.0})      # AdamW's add, copy, stranger
    assert owned.missing == {"stranger.3": 1.0}
    assert owned.mixed_s == pytest.approx([2.0 + 4.0, 2.0 + 6.0])
    # every step's owners sum to its busy time: the pieces do not overlap
    assert [sum(step.values()) for step in owned.per_step] == \
        pytest.approx([19.0, 20.0])


def test_a_number_is_the_median_over_the_steps():
    owned = owners.join(_scoped(), MAP)
    assert owned.owner_ms("hvd.optimizer_update") == pytest.approx(3750.0)
    assert owned.ms(lambda o, d: d == R) == pytest.approx(1250.0)
    assert owned.busy_ms() == pytest.approx(19500.0)
    assert owned.mixed_ms() == pytest.approx(7000.0)
    assert owned.owner_ms("hvd.flash_attention") is None    # never seen
    assert owners.join(_scoped(steps=[]), MAP) is None


def test_events_between_the_steps_count_for_nothing():
    owned = owners.join(_scoped(), MAP)
    assert owners.join(_scoped([("copy-done.7", 20.5, 1.0)]), MAP) is None
    assert owned.per_step[1][(ho.UNOWNED, F)] == pytest.approx(1.5 + 1.0)


def test_a_piece_is_clipped_to_its_step():
    owned = owners.join(_scoped([("qkv_fusion", 19.0, 2.0)]), MAP)
    assert owned.per_step[0] == {("hvd.attn_proj", F): pytest.approx(1.0)}


def test_events_a_step_counts_instructions_by_prefix_and_owner():
    owned = owners.join(_scoped(), MAP)
    ops = _scoped().ops
    assert owned.events_per_step(ops, "ragged-dot-metadata",
                                 ("hvd.moe_ffn", B)) == 2.5
    assert owned.events_per_step(ops, "ragged-dot-metadata",
                                 ("hvd.moe_ffn", R)) == 0.5
    assert owned.events_per_step(ops, "ragged-dot-metadata",
                                 ("hvd.moe_ffn", F)) is None
    # a fusion of two owners is no one's whole
    assert owned.events_per_step(ops, "norm_rotary", ("hvd.norm", F)) is None


# -- the readers --------------------------------------------------------------


def _perf_layers() -> set:
    """The rows of PERF.md section 3's table of layers."""
    with open(os.path.join(mf.ROOT, "PERF.md")) as f:
        section = f.read().split("\n## 3. Layers", 1)[1].split("\n## ")[0]
    rows = re.findall(r"^\| ([^|]+?) \|", section, flags=re.M)
    return set(rows) - {"Layer", "---"}


@pytest.mark.parametrize("name", READERS)
def test_reader_is_declared_where_it_has_to_be(name):
    reader = mf.load_module("layers", name)
    manifest = mf.load()
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert (entry["name"], entry["unit"], entry["layer"], entry["moves"]) \
        == (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES)
    assert entry["source"] == "program_span"
    assert entry["workloads"] == CELLS.get(name, GPT2 + [SPARSE, TRINITY])
    assert reader.LAYER in _perf_layers()
    assert mf.validate(manifest) == []
    if hasattr(reader, "SCOPE"):
        assert reader.SCOPE in DEVICE_SCOPES
    # nothing traced: nothing, no error
    assert reader.read(_run()) is None
    # a program whose text names none of this reader's work: nothing, but
    # for the two shares of the busy time, which then read a measured 0
    bare = _owned_run(owners.join(_scoped([("copy.1", 0.0, 1.0)]),
                                  {"copy.1": {("hvd.bucket_pack", F): 1.0}}))
    assert reader.read(bare) == (0.0 if name.endswith("_pct") else None)


def test_new_entries_stand_at_the_end_of_the_manifest():
    names = [m["name"] for m in mf.load()["per_layer"]]
    assert tuple(names[-len(READERS):]) == READERS


def test_readers_on_the_hand_made_steps():
    run = _owned_run(owners.join(_scoped(), MAP))
    got = {name: mf.load_module("layers", name).read(run)
           for name in READERS}
    assert got == pytest.approx({
        "proj.ms": 2e3, "mlp.ms": 3e3, "norm.ms": 2e3, "rotary.ms": 1e3,
        "grad.unowned_ms": 1e3, "grad.remat_ms": 1250.0,
        "optimizer.ms": 3750.0,
        "step.unowned_pct": 100 * 2.75 / 19.5,
        "step.mixed_pct": 100 * 7.0 / 19.5, "moe.tiles_per_step": 2.5})


# -- a program without the map, a run without a trace -------------------------


def test_of_reads_nothing_where_there_is_nothing(monkeypatch):
    assert owners.of(_run()) is None                    # not traced
    run = _run()
    run.scoped_ops = _scoped()
    monkeypatch.setattr(owners, "hlo_owners", None)     # the parent commit
    assert owners.of(run) is None and run.notes == []
    for name in READERS:
        assert mf.load_module("layers", name).read(run) is None


# -- pieces recorded on the chip ----------------------------------------------


def _recorded(name):
    with gzip.open(os.path.join(DATA, name)) as f:
        d = json.load(f)
    return tr.Trace.from_json(d), scopes.ScopedOps.from_json(d)


def _map_by_root(scoped) -> dict:
    """What rule 2 alone would say: every instruction its own path's."""
    return {ho.instruction_name(op[0]): {ho.key_of(op[3]): 1.0}
            for op in scoped.ops}


def test_recorded_join_by_the_roots_is_the_scopes_own_partition():
    """Two steps of gpt2-medium (PR 24's program): with a map that knows
    only each event's own path the owners' partition is ``lib/scopes.py``'s,
    and it sums to what ``step.device_busy_ms`` reads."""
    trace, scoped = _recorded("trace_1chip_scoped.json.gz")
    owned = owners.join(scoped, _map_by_root(scoped))
    assert owned.missing == {} and owned.mixed_ms() == 0.0
    classes = scoped.classes_ms()
    assert owned.owner_ms("hvd.optimizer_update") == pytest.approx(
        classes["hvd.optimizer_update"], rel=1e-6)
    assert owned.owner_ms(ho.UNOWNED) == pytest.approx(
        classes[scopes.UNSCOPED], rel=1e-6)
    assert owned.ms(lambda o, d: o not in (
        ho.UNOWNED, "hvd.optimizer_update", "hvd.bucket_pack",
        "hvd.bucket_unpack", "hvd.allreduce_grads") and d == B) == \
        pytest.approx(classes["hvd.grad.backward"], rel=1e-6)
    busy = tr.step_busy_seconds(trace, 0)
    assert owned.busy_ms() == pytest.approx(1e3 * sum(busy) / 2, rel=1e-5)


def test_recorded_join_moves_a_fusions_time_to_what_is_inside():
    """PR 24's finding on its own trace: hand AdamW's fusions (their root
    is ``apply_updates``' unscoped add, 7.9 ms a step) to the optimizer and
    the path-less copies to the matmuls they feed, and ``optimizer.ms``
    reads what ``step.optimizer_ms`` could not, out of the unowned share."""
    _, scoped = _recorded("trace_1chip_scoped.json.gz")
    by_root = _map_by_root(scoped)
    plain = owners.join(scoped, by_root)
    opt, proj = ("hvd.optimizer_update", F), ("hvd.attn_proj", F)
    moved = dict(by_root)
    adamw = copies = 0
    for op in scoped.ops:
        name = ho.instruction_name(op[0])
        if "jit(spmd)/add" in op[3]:
            moved[name] = {opt: 0.9, (ho.UNOWNED, F): 0.1}
            adamw += 1
        elif not op[3] and name.startswith(("copy-", "slice-")):
            moved[name] = {proj: 1.0}
            copies += 1
    assert adamw > 100 and copies > 1000
    owned = owners.join(scoped, moved)
    gained = owned.owner_ms(opt[0]) - plain.owner_ms(opt[0])
    assert gained > 5.0                                  # ms a step
    assert owned.owner_ms(proj[0]) > 5.0 and plain.owner_ms(proj[0]) is None
    assert owned.owner_ms(ho.UNOWNED) < plain.owner_ms(ho.UNOWNED) - gained
    assert owned.busy_ms() == pytest.approx(plain.busy_ms(), rel=1e-9)
    assert owned.mixed_ms() == pytest.approx(gained / 0.9, rel=1e-6)


def test_recorded_tiles_are_the_metadata_calls_of_the_backward_bodies():
    """One step of trinity-mini (PR 30's program, before the walk: one
    grouped call a direction and a layer): the op the readers count is in
    the trace under its own name, once a call."""
    _, scoped = _recorded("trace_trinity_scoped.json.gz")
    calls = sorted({ho.instruction_name(op[0]) for op in scoped.ops
                    if op[0].startswith("%ragged-dot-metadata")})
    assert len(calls) == 12             # 4 routed layers x 3 directions
    owned = owners.join(scoped, {
        **_map_by_root(scoped),
        **{name: {("hvd.moe_ffn", B if i % 3 == 2 else F): 1.0}
           for i, name in enumerate(calls)}})
    run = _run()
    run.owned, run.scoped_ops = owned, scoped
    assert mf.load_module("layers", "moe.tiles_per_step").read(run) == 4


# -- the program from the trace, at the tiny size -----------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _metadata_plane(protos: dict, plane=owners.METADATA_PLANE) -> bytes:
    """An encoded XSpace with the plane the TPU profiler writes the traced
    programs into: {module name: HloProto bytes}."""
    body = _field(2, plane)
    body += _field(5, _field(1, 3) + _field(2, _field(1, 3)
                                            + _field(2, owners.HLO_STAT)))
    for key, (name, proto) in enumerate(protos.items(), start=1):
        stat = _field(1, 3) + _field(6, proto)
        body += _field(4, _field(1, key) + _field(2, _field(
            1, key) + _field(2, name) + _field(5, stat)))
    return _field(1, _field(2, "/host:CPU")) + _field(1, body)


@pytest.fixture(scope="module")
def tiny_module():
    """(HloModuleProto bytes, as_text()) of the tiny GPT step compiled for
    one CPU device."""
    from benchmarks.builders import gpt_decoder

    try:
        session = gpt_decoder.build(tiny.CONFIG, tiny.JOB, jax.devices()[:1])
        compiled = session.lower(session.abstract_args()).compile()
        module = compiled.runtime_executable().hlo_modules()[0]
        yield module.as_serialized_hlo_module_proto(), compiled.as_text()
    finally:
        hvd.shutdown()     # the builder owns init/shutdown: hand the
        hvd.init()         # other tests their mesh back


def test_the_trace_holds_the_program_it_traced(tiny_module, tmp_path):
    proto, text = tiny_module
    step, other = "jit_spmd(123)", "jit_convert_element_type(9)"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_metadata_plane({other: _field(1, b""),
                                      step: _field(1, proto)}))
    printed = owners.traced_hlo(str(path), {step})
    # XLA's own printer: the map of it is the map of compiled.as_text()
    assert ho.owners(printed) == ho.owners(text)
    assert owners.traced_hlo(str(path), {"jit_spmd(124)"}) is None
    path.write_bytes(_metadata_plane({step: _field(1, proto)},
                                     plane="/host:other"))
    assert owners.traced_hlo(str(path), {step}) is None


def test_the_traced_program_is_read_and_joined(tiny_module, tmp_path,
                                               monkeypatch):
    """What a traced run does after its window, at the tiny size: the
    program from the trace's own proto under the steps' module name, the
    map, the join, the two lines it prints, the ten readers."""
    proto, text = tiny_module
    step = "jit_spmd(123)"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_metadata_plane({step: _field(1, proto)}))
    # one event of 1 us for every instruction of the ENTRY computation,
    # in two steps, and a stranger
    entry = list(ho.parse(text).values())[-1]
    per_step = len(entry) * 1e-6
    events = [(i.name, at * 2 * per_step + n * 1e-6, 1e-6)
              for at in range(2) for n, i in enumerate(entry)]
    events.append(("fusion.99999", 3 * per_step, 1e-6))
    steps = [(0.0, per_step), (2 * per_step, 4 * per_step)]
    run = _run(trace=types.SimpleNamespace(
        ops={0: []}, modules={0: [(step, a, b - a) for a, b in steps]
                              + [("jit_copy(7)", per_step, 1e-6)]}))
    run.scoped_ops = _scoped(events, steps)
    monkeypatch.setattr(scopes, "newest_xplane", lambda: str(path))
    owned = owners.of(run)
    assert owners.of(run) is owned                       # made once
    assert owned.missing == {"fusion.99999": pytest.approx(1e-6)}
    assert "['jit_spmd(123)'] printed from the trace's own proto" \
        in run.notes[0]
    assert "not in it (1): fusion.99999" in run.notes[0]
    assert re.search(r"sum [\d.]+ against busy [\d.]+ ms", run.notes[1])
    got = {name: mf.load_module("layers", name).read(run)
           for name in READERS}
    # the tiny GPT step: no rotation, no remat, no routed experts
    assert {k for k, v in got.items() if v is None} == {
        "rotary.ms", "grad.remat_ms", "moe.tiles_per_step"}
    assert got["optimizer.ms"] > 0 and got["norm.ms"] > 0
    assert got["proj.ms"] > 0 and got["mlp.ms"] > 0
    assert 0 < got["step.unowned_pct"] < 100 and got["step.mixed_pct"] > 0
    # a trace without the program: nothing, no error
    path.write_bytes(_metadata_plane({}))
    bare = _run(trace=run.trace)
    bare.scoped_ops = run.scoped_ops
    assert owners.of(bare) is None and bare.notes == []
