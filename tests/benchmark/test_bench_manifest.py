"""BENCHMARK.json meets the benchmark's contract as far as that shows
without a run, and every file it names is found by name."""

import copy
import os

import pytest

from benchmarks.lib import manifest as mf, peaks, traffic

MANIFEST = mf.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = [m["name"] for m in MANIFEST["end_to_end"]]
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]


def test_manifest_is_valid():
    assert mf.validate(MANIFEST) == []


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_files_are_found_by_name(cell_name):
    cell = mf.cell(MANIFEST, cell_name)
    assert cell_name == f"{cell['config']}.{cell['traffic']}"
    config = mf.config_of(MANIFEST, cell["config"])
    traffic.validate_job(mf.job_of(cell["traffic"]))
    limits = mf.limits_of(cell_name)
    assert limits["steps"] >= 1 and "set_from" in limits
    assert hasattr(mf.load_module("builders", config["builder"]), "build")
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == cell["config"])
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert any(key in text for text in config["departures"])


@pytest.mark.parametrize("name", E2E)
def test_end_to_end_metric_has_its_reader(name):
    entry = next(m for m in MANIFEST["end_to_end"] if m["name"] == name)
    mod = mf.load_module("end_to_end", name)
    assert (mod.NAME, mod.UNIT) == (name, entry["unit"])
    assert callable(mod.read)


@pytest.mark.parametrize("name", PER_LAYER)
def test_per_layer_metric_has_its_reader(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    mod = mf.load_module("layers", name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        name, entry["unit"], entry["layer"], entry["moves"])
    assert callable(mod.read)


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_cell_reports_step_p90_on_one_side(cell_name):
    """Bounded end to end where a step does not follow its routing, as
    ``step.interval_p90_ms`` of the traced window where it does (PERF.md,
    PR 35): each cell has one of the two, and a rate either way."""
    e2e = {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end",
                                             cell_name)}
    layers = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer",
                                                cell_name)}
    assert ("step_ms_p90" in e2e) != ("step.interval_p90_ms" in layers)
    assert {"tokens_per_s_per_chip", "setup_s"} <= e2e


@pytest.mark.parametrize("count, expect", [(9, None), (11, 109.0)])
def test_interval_p90_reader(count, expect):
    class Run:
        def intervals(self):
            return [0.1 + 0.001 * i for i in range(count)]

    value = mf.load_module("layers", "step.interval_p90_ms").read(Run())
    assert value == (expect if expect is None
                     else pytest.approx(expect, rel=1e-9))


def test_every_reader_file_is_in_the_manifest():
    for kind, names in (("end_to_end", E2E), ("layers", PER_LAYER)):
        files = {f[:-3] for f in os.listdir(os.path.join(mf.BENCH, kind))
                 if f.endswith(".py")}
        assert files == set(names)


def _broken(edit):
    m = copy.deepcopy(MANIFEST)
    edit(m)
    return m


BREACHES = {
    "name_with_space": lambda m: m["workloads"][0].update(name="a cell"),
    "unit_with_space": lambda m: m["end_to_end"][0].update(
        unit="tokens per s"),
    "greek_unit": lambda m: m["per_layer"][0].update(unit="µs"),
    "bound_too_wide": lambda m: m["end_to_end"][0].update(bound=0.2),
    "no_setup_s": lambda m: m.update(end_to_end=[
        e for e in m["end_to_end"] if e["name"] != "setup_s"]),
    "extra_key_on_metric": lambda m: m["per_layer"][0].update(why="x"),
    "reduced_names_a_width": lambda m: m["configs"][0]["reduced"].append(
        "n_embd"),
    "unknown_traffic_cell_in_metric": lambda m: m["per_layer"][0].update(
        workloads=["nope"]),
    "five_chips": lambda m: m["workloads"][0].update(chips=5),
    "extra_top_level_key": lambda m: m.update(notes="x"),
    "command_outside_paths": lambda m: m.update(
        command=["python3", "chip_smoke.py"]),
    "run_seconds_too_long": lambda m: m.update(run_seconds=52),
}


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_validate_names_the_breach(breach):
    assert mf.validate(_broken(BREACHES[breach])) != []


def test_peaks_have_sources_and_unknown_kind_is_an_error():
    table = peaks.load()
    for kind, entry in table.items():
        assert peaks.for_device_kind(kind, table)["source"]
        assert all(entry[k] > 0 for k in peaks.FIELDS if k != "source")
    assert peaks.for_device_kind("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in"):
        peaks.for_device_kind("TPU v5")
    with pytest.raises(KeyError):
        peaks.for_device_kind("cpu")
