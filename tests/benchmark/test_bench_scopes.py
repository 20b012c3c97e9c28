"""The device trace read by the program's scope names
(benchmarks/lib/scopes.py): the rules of the reduction on hand-made ops,
the ``.xplane.pb`` reader on a hand-encoded file, the eight readers, and
the same functions on a piece recorded on the chip
(data/trace_1chip_scoped.json.gz says where it came from)."""

import gzip
import json
import os
import re
import types

import pytest

from benchmarks.lib import manifest as mf, scopes, trace as tr
from horovod_tpu.monitor.span_audit import DEVICE_SCOPES

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("step.forward_ms", "step.backward_ms", "step.optimizer_ms",
           "step.unscoped_pct", "head_loss.ms", "attention.ms",
           "attention.layout_ms", "collective.pack_ms")

FWD = "jit(spmd)/shard_map/hvd.grad/jvp(GPT)/h0/mlp/dot_general"
BWD = "jit(spmd)/shard_map/hvd.grad/transpose(hvd.grad)/jvp(GPT)/h0/mlp/mul"
ATTN_F = ("jit(spmd)/shard_map/hvd.grad/jvp(GPT)/h0/attn/"
          "hvd.flash_attention/")
ATTN_B = ("jit(spmd)/shard_map/hvd.grad/transpose(hvd.grad)/jvp(GPT)/h0/"
          "attn/hvd.flash_attention/")
HEAD_B = ("jit(spmd)/shard_map/hvd.grad/transpose(hvd.grad)/"
          "jvp(hvd.lm_head_loss)/dot_general")
PACK = "jit(spmd)/shard_map/hvd.allreduce_grads/hvd.bucket_pack/concatenate"
WIRE = "jit(spmd)/shard_map/hvd.allreduce_grads/hvd.bucket_allreduce/psum"
UNPACK = "jit(spmd)/shard_map/hvd.allreduce_grads/hvd.bucket_unpack/slice"
OPT = "jit(spmd)/shard_map/hvd.optimizer_update/mul"
PLAIN = "jit(spmd)/shard_map/add"


def _scoped(ops, steps=None):
    ops = [(f"%op.{i} = f32[] fusion()", s, d, p) if isinstance(p, str)
           else (p[0], s, d, p[1]) for i, (s, d, p) in enumerate(ops)]
    steps = steps or [(min(o[1] for o in ops),
                       max(o[1] + o[2] for o in ops))]
    return scopes.ScopedOps(ops, steps)


# One step of 20 s: forward 0-4 (attention kernel 1-2, its layout 2-3),
# backward 4-10 (head 4-6, attention kernel 7-8), pack 10-11, wire 11-13,
# unpack 13-14, optimizer 14-17, an unscoped add 17-18, a copy with no
# path at all 18-20.
STEP = [(0.0, 1.0, FWD), (1.0, 1.0, ("%hvd_flash_fwd.3 = (bf16[8]) "
                                    "custom-call()",
                                    ATTN_F + "hvd_flash_fwd/pallas_call")),
        (2.0, 1.0, ATTN_F + "transpose"), (3.0, 1.0, FWD),
        (4.0, 2.0, HEAD_B), (6.0, 1.0, BWD),
        (7.0, 1.0, ("%hvd_flash_bwd_dq.3 = bf16[8] custom-call()",
                    ATTN_B + "hvd_flash_bwd_dq/pallas_call")),
        (8.0, 2.0, BWD), (10.0, 1.0, PACK), (11.0, 2.0, WIRE),
        (13.0, 1.0, UNPACK), (14.0, 3.0, OPT), (17.0, 1.0, PLAIN),
        (18.0, 2.0, "")]


def test_nested_scopes_each_see_the_event():
    s = _scoped(STEP)
    assert s.scope_ms("hvd.flash_attention") == pytest.approx(3e3)
    assert s.scope_ms("hvd.lm_head_loss") == pytest.approx(2e3)
    # ... and the scope around them sees them too
    assert s.scope_ms("hvd.grad") == pytest.approx(10e3)
    assert s.scope_ms("hvd.allreduce_grads") == pytest.approx(4e3)
    assert s.scope_ms("hvd.bucket_pack", "hvd.bucket_unpack") == \
        pytest.approx(2e3)


def test_transpose_flips_the_direction():
    s = _scoped(STEP)
    assert s.scope_ms("hvd.grad", direction="forward") == pytest.approx(4e3)
    assert s.scope_ms("hvd.grad", direction="backward") == \
        pytest.approx(6e3)
    assert s.scope_ms("hvd.flash_attention", direction="backward") == \
        pytest.approx(1e3)
    assert s.scope_ms("hvd.optimizer_update", direction="backward") is None


def test_the_outermost_name_decides_the_class():
    assert scopes.outermost_class(ATTN_B + "mul") == "hvd.grad.backward"
    assert scopes.outermost_class(ATTN_F + "mul") == "hvd.grad.forward"
    assert scopes.outermost_class(HEAD_B) == "hvd.grad.backward"
    assert scopes.outermost_class(UNPACK) == "hvd.allreduce_grads"
    assert scopes.outermost_class(OPT) == "hvd.optimizer_update"
    assert scopes.outermost_class(PLAIN) == scopes.UNSCOPED
    assert scopes.outermost_class("") == scopes.UNSCOPED
    # a scope used outside hvd.grad is its own class
    assert scopes.outermost_class("jit(f)/hvd.flash_attention/mul") == \
        "hvd.flash_attention"


def test_classes_sum_to_the_busy_union():
    s = _scoped(STEP)
    classes = s.classes_ms()
    assert classes == pytest.approx({
        "hvd.grad.forward": 4e3, "hvd.grad.backward": 6e3,
        "hvd.allreduce_grads": 4e3, "hvd.optimizer_update": 3e3,
        scopes.UNSCOPED: 3e3})
    busy = tr.measure(tr.union([(o[1], o[1] + o[2]) for o in s.ops]))
    assert sum(classes.values()) == pytest.approx(busy * 1e3)


def test_nested_events_count_once_as_the_innermost():
    """A while loop's event holds its body's events on the same line: the
    time goes to the body where there is one, to the loop elsewhere."""
    s = _scoped([(0.0, 10.0, OPT), (2.0, 3.0, FWD), (3.0, 1.0, BWD),
                 (6.0, 2.0, PLAIN), (10.0, 1.0, PLAIN)])
    pieces = [(a, b, op[3]) for a, b, op in s.innermost]
    assert pieces == [(0.0, 2.0, OPT), (2.0, 3.0, FWD), (3.0, 4.0, BWD),
                      (4.0, 5.0, FWD), (5.0, 6.0, OPT), (6.0, 8.0, PLAIN),
                      (8.0, 10.0, OPT), (10.0, 11.0, PLAIN)]
    assert s.classes_ms() == pytest.approx({
        "hvd.optimizer_update": 5e3, "hvd.grad.forward": 2e3,
        "hvd.grad.backward": 1e3, scopes.UNSCOPED: 3e3})


def test_a_number_is_the_median_over_the_steps():
    ops = [(0.0, 1.0, OPT), (10.0, 2.0, OPT), (20.0, 6.0, OPT),
           (1.0, 1.0, PLAIN)]
    s = _scoped(ops, steps=[(0.0, 10.0), (10.0, 20.0), (20.0, 30.0)])
    assert s.scope_ms("hvd.optimizer_update") == pytest.approx(2e3)
    assert s.calls_per_step("hvd.optimizer_update") == 1


def test_a_scope_never_seen_reads_nothing():
    s = _scoped(STEP)
    assert s.scope_ms("hvd.layer_norm") is None
    bare = _scoped([(0.0, 1.0, PLAIN), (1.0, 1.0, "")])
    assert bare.classes_ms() is None      # a program without the scopes
    assert bare.scope_ms("hvd.grad") is None


# -- the .xplane.pb reader ----------------------------------------------------


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


def _xplane(name: str, line: str, events, metadata, stat_names) -> bytes:
    """An encoded XPlane: ``events`` [(metadata_id, offset_ps, dur_ps)],
    ``metadata`` {id: (name, [XStat bytes])}, ``stat_names`` {id: name}."""
    body = _field(2, name)
    for k, v in stat_names.items():
        body += _field(5, _field(1, k) + _field(2, _field(1, k)
                                                + _field(2, v)))
    for k, (md_name, stats) in metadata.items():
        md = _field(1, k) + _field(2, md_name)
        for stat in stats:
            md += _field(5, stat)
        body += _field(4, _field(1, k) + _field(2, md))
    xline = _field(2, line) + _field(3, 5)      # timestamp_ns = 5
    for md_id, offset, dur in events:
        # a double-valued stat too: fixed-width fields must be skipped
        stat = _field(1, 9) + _varint(2 << 3 | 1) + b"\0" * 8
        xline += _field(4, _field(1, md_id) + _field(2, offset)
                        + _field(3, dur) + _field(4, stat))
    return _field(1, body + _field(3, xline))


def test_load_reads_the_path_from_the_event_metadata(tmp_path):
    stat_names = {7: "hlo_category", 8: scopes.PATH_STAT, 9: "Time Scale",
                  10: FWD}
    metadata = {
        1: ("%fusion.1 = f32[8] fusion()",
            [_field(1, 7) + _field(5, "loop fusion"),
             _field(1, 8) + _field(5, OPT)]),
        2: ("%dot.2 = f32[8] dot()", [_field(1, 8) + _field(7, 10)]),
        3: ("%copy-done.3 = f32[8] copy-done()",
            [_field(1, 7) + _field(5, "copy-done")])}
    events = [(2, 3000, 2000), (1, 1000, 1000), (3, 5000, 500)]
    space = (_xplane("/host:CPU", "python3", [(1, 0, 10)], {}, {})
             + _xplane("/device:TPU:1", tr.OPS_LINE, [(3, 0, 1)], metadata,
                       stat_names)
             + _xplane("/device:TPU:0", tr.OPS_LINE, events, metadata,
                       stat_names))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    ops = scopes.load(str(path))       # the first device, sorted by start
    assert [(n.split(" ")[0], p) for n, _, _, p in ops] == [
        ("%fusion.1", OPT), ("%dot.2", FWD), ("%copy-done.3", "")]
    assert [(s, d) for _, s, d, _ in ops] == pytest.approx(
        [(6e-9, 1e-9), (8e-9, 2e-9), (10e-9, 0.5e-9)])
    path.write_bytes(_xplane("/host:CPU", "python3", [(1, 0, 10)], {}, {}))
    assert scopes.load(str(path)) == []


def _run(trace=None, chips=1):
    notes = []
    return types.SimpleNamespace(trace=trace, chips=chips,
                                 note=notes.append, notes=notes)


def test_of_reads_nothing_it_cannot_match(tmp_path, monkeypatch):
    assert scopes.of(_run()) is None                    # not traced
    trace = tr.Trace({0: [("a", 0.0, 1.0)]}, {0: []},
                     {0: [("jit_step", 0.0, 1.0)]}, [], (0.0, 1.0))
    monkeypatch.setattr(scopes, "newest_xplane", lambda: None)
    assert scopes.of(_run(trace)) is None               # no file
    path = tmp_path / "t.xplane.pb"
    md = {1: ("a", [_field(1, 8) + _field(5, OPT)])}
    path.write_bytes(_xplane("/device:TPU:0", tr.OPS_LINE,
                             [(1, 0, 10), (1, 20, 10)], md,
                             {8: scopes.PATH_STAT}))
    monkeypatch.setattr(scopes, "newest_xplane", lambda: str(path))
    assert scopes.of(_run(trace)) is None               # another run's file
    trace.ops[0].append(("a", 2.0, 1.0))
    run = _run(trace)
    assert len(scopes.of(run).ops) == 2 and "2 with a path" in run.notes[0]
    assert scopes.of(run) is run.scoped_ops             # read once


def test_newest_xplane_is_the_newest(tmp_path):
    assert scopes.newest_xplane(str(tmp_path)) is None
    for i, cell in enumerate(("a", "b")):
        d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "h.xplane.pb").write_bytes(b"")
        os.utime(d / "h.xplane.pb", (100 + i, 100 + i))
    assert "/b/" in scopes.newest_xplane(str(tmp_path))


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
    assert reader.LAYER in _perf_layers()
    assert mf.validate(manifest) == []
    named = getattr(reader, "SCOPES", None) or (
        (reader.SCOPE,) if hasattr(reader, "SCOPE") else ())
    assert all(scope in DEVICE_SCOPES for scope in named)
    assert named or name == "step.unscoped_pct"
    # nothing traced, or a program without the scopes: nothing, no error
    assert reader.read(_run()) is None
    bare = _run(tr.Trace({0: [("a", 0.0, 1.0)]}, {0: []},
                         {0: [("jit_step", 0.0, 1.0)]}, [], (0.0, 1.0)))
    bare.scoped_ops = _scoped([(0.0, 1.0, PLAIN)])
    assert reader.read(bare) is None


def test_readers_on_the_hand_made_step():
    trace = tr.Trace({0: [(f"op{i}", s, d) for i, (s, d, _) in
                          enumerate(STEP)]}, {0: []},
                     {0: [("jit_step", 0.0, 20.0)]}, [], (0.0, 20.0))
    run = _run(trace, chips=4)
    run.scoped_ops = _scoped(STEP)
    got = {name: mf.load_module("layers", name).read(run)
           for name in READERS}
    assert got == pytest.approx({
        "step.forward_ms": 4e3, "step.backward_ms": 6e3,
        "step.optimizer_ms": 3e3, "step.unscoped_pct": 15.0,
        "head_loss.ms": 2e3, "attention.ms": 3e3,
        "attention.layout_ms": 1e3, "collective.pack_ms": 2e3})
    assert any("sum 20000.000 against busy 20000.000" in n
               for n in run.notes)
    assert any("hvd_flash_fwd 1" in n for n in run.notes)


def test_layout_needs_named_kernels():
    """Kernels the trace cannot tell from their surroundings (a program
    before the ``name=``): no layout number, rather than all of attention
    under its name."""
    ops = [(s, d, p[1].replace("hvd_flash_fwd/", "").replace(
        "hvd_flash_bwd_dq/", "") if not isinstance(p, str) else p)
        for s, d, p in STEP]
    run = _run()
    run.scoped_ops = _scoped(ops)
    assert mf.load_module("layers", "attention.layout_ms").read(run) is None
    assert mf.load_module("layers", "attention.ms").read(run) == \
        pytest.approx(3e3)


# -- the recorded piece -------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "trace_1chip_scoped.json.gz")) as f:
        d = json.load(f)
    return tr.Trace.from_json(d), scopes.ScopedOps.from_json(d)


def test_recorded_classes_partition_the_busy_time(recorded):
    """Two steps of gpt2-medium on the chip: the five classes sum to what
    step.device_busy_ms reads, and the backward pass is 1.88 forwards."""
    trace, scoped = recorded
    assert len(scoped.steps) == 2
    classes = scoped.classes_ms()
    assert classes == pytest.approx({
        "hvd.grad.forward": 60.465, "hvd.grad.backward": 113.616,
        "hvd.allreduce_grads": 0.519, "hvd.optimizer_update": 0.696,
        scopes.UNSCOPED: 21.252}, abs=2e-3)
    busy = tr.step_busy_seconds(trace, 0)
    assert sum(classes.values()) == pytest.approx(
        1e3 * sum(busy) / 2, rel=1e-5)
    # no event is nested in another on this runtime's ops line
    assert len(scoped.innermost) == len(scoped.ops)


def test_recorded_kernels_are_told_by_their_names(recorded):
    _, scoped = recorded
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert scoped.calls_per_step(kernel) == 24      # one a layer
        ops = [op for op in scoped.ops if kernel + "." in op[0]]
        assert len(ops) == 48
        # in the HLO instruction's name and in the path, under the scope
        assert all(f"hvd.flash_attention/{kernel}/pallas_call" in op[3]
                   for op in ops)
        assert all(("transpose(" in op[3]) == ("bwd" in kernel)
                   for op in ops)


RECORDED = {"step.forward_ms": 60.465, "step.backward_ms": 113.616,
            "step.optimizer_ms": 0.696, "step.unscoped_pct": 10.813,
            "head_loss.ms": 17.537, "attention.ms": 65.170,
            "attention.layout_ms": 10.785, "collective.pack_ms": 0.519}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_recorded_piece(name, recorded):
    trace, scoped = recorded
    run = _run(trace)
    run.scoped_ops = scoped
    assert mf.load_module("layers", name).read(run) == pytest.approx(
        RECORDED[name], abs=2e-3)
