"""``BENCHMARK.json`` and the files it names, found by name.

Nothing here holds the name of a configuration, a job, a cell or a metric:
a cell ``<config>.<traffic>`` is ``benchmarks/configs/<config>.json`` (the
manifest's ``file``) under ``benchmarks/jobs/<traffic>.json``, held to
``benchmarks/limits/<cell>.json``; a metric ``<name>`` is read by
``benchmarks/end_to_end/<name>.py`` or ``benchmarks/layers/<name>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "n_embd",
               "n_inner", "d_model", "d_ff", "head_dim", "expansion",
               "experts_per_tok")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                   f"{[w['name'] for w in manifest['workloads']]})")


def config_of(manifest: dict, name: str, root: str = ROOT) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return _load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def job_of(traffic: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "benchmarks", "jobs",
                                   traffic + ".json"))


def limits_of(cell_name: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "benchmarks", "limits",
                                   cell_name + ".json"))


def load_module(kind: str, name: str):
    """The module ``benchmarks/<kind>/<name>.py`` (``kind`` one of
    ``builders``, ``end_to_end``, ``layers``); metric names hold dots, so
    the file is loaded by path."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r} has no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(manifest: dict, section: str, cell_name: str) -> list:
    """The manifest's metrics of ``section`` that ``cell_name`` reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def _line(text, what: str, errors: list) -> None:
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        errors.append(f"{what}: not one line of 1..200 characters")


def validate(manifest: dict, root: str = ROOT) -> list:
    """Every breach of the benchmark's contract that can be seen without a
    run, as a list of sentences (empty when the manifest is sound)."""
    err: list = []
    if set(manifest) != TOP_KEYS:
        err.append(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
        return err
    cmd, paths = manifest["command"], manifest["paths"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        err.append("command: 1..32 strings")
    for word in cmd:
        _line(word, f"command word {word!r}", err)
        if word.startswith("/") or ".." in word.split("/"):
            err.append(f"command word {word!r} leaves the repo")
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        err.append("paths: 1..16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            err.append(f"path {p!r} is not a relative path of allowed "
                       f"characters")
        elif not os.path.isdir(os.path.join(root, p)):
            err.append(f"path {p!r} is not a directory")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in paths)

    for word in cmd:
        if os.path.exists(os.path.join(root, word)) and not under_paths(word):
            err.append(f"command names {word!r}, a file outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        err.append("run_seconds: a whole number 1..51")

    names: set = set()

    def fresh(name, what):
        if not (isinstance(name, str) and NAME.match(name)):
            err.append(f"{what} name {name!r} has characters a name may not")
        if name in names:
            err.append(f"{what} name {name!r} is used twice")
        names.add(name)

    configs = manifest["configs"]
    if not 1 <= len(configs) <= 24:
        err.append("configs: 1..24")
    files = set()
    for c in configs:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            err.append(f"config {c.get('name')!r}: keys {sorted(c)}")
            continue
        fresh(c["name"], "config")
        _line(c["source"], f"config {c['name']} source", err)
        _line(c["why"], f"config {c['name']} why", err)
        if not under_paths(c["file"]) or c["file"] in files:
            err.append(f"config file {c['file']!r} is outside paths or "
                       f"used twice")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(root, c["file"])):
            err.append(f"config file {c['file']!r} not found")
        if len(c["reduced"]) > 16:
            err.append(f"config {c['name']}: more than 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key):
                err.append(f"reduced key {key!r} is not a name")
            if (any(w in key for w in WIDTH_WORDS)
                    or key.endswith(("_dim", "_rank"))):
                err.append(f"config {c['name']}: reduced names a width "
                           f"({key!r})")
    config_names = {c["name"] for c in configs if "name" in c}

    names = set()
    cells = manifest["workloads"]
    if not 1 <= len(cells) <= 24:
        err.append("workloads: 1..24")
    pairs = set()
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            err.append(f"workload {w.get('name')!r}: keys {sorted(w)}")
            continue
        fresh(w["name"], "workload")
        if w["config"] not in config_names:
            err.append(f"workload {w['name']}: unknown config")
        if not NAME.match(w["traffic"]):
            err.append(f"workload {w['name']}: traffic is not a name")
        if w["chips"] not in (1, 4):
            err.append(f"workload {w['name']}: chips must be 1 or 4")
        _line(w["why"], f"workload {w['name']} why", err)
        if (w["config"], w["traffic"]) in pairs:
            err.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    used = {w.get("config") for w in cells}
    for c in config_names - used:
        err.append(f"config {c!r} is used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        err.append(f"{four} four-chip cells of {len(cells)}: over a quarter")
    cell_names = {w["name"] for w in cells if "name" in w}

    names = set()
    e2e, per_layer = manifest["end_to_end"], manifest["per_layer"]
    if not 1 <= len(e2e) <= 16:
        err.append("end_to_end: 1..16")
    if not 1 <= len(per_layer) <= 128:
        err.append("per_layer: 1..128")
    for section, metrics, keys in (
            ("end_to_end", e2e,
             {"name", "unit", "better", "bound", "source"}),
            ("per_layer", per_layer,
             {"name", "unit", "better", "source", "layer", "moves"})):
        for m in metrics:
            if set(m) - {"workloads"} != keys:
                err.append(f"{section} {m.get('name')!r}: keys {sorted(m)}")
                continue
            fresh(m["name"], "metric")
            if not UNIT.match(m["unit"]):
                err.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                err.append(f"metric {m['name']}: better")
            if m["source"] not in SOURCES:
                err.append(f"metric {m['name']}: source {m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cell_names:
                    err.append(f"metric {m['name']}: unknown cell {w!r}")
            if section == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    err.append(f"metric {m['name']}: an end-to-end metric "
                               f"is taken by the benchmark itself")
                if not (isinstance(m["bound"], (int, float))
                        and 0.01 <= m["bound"] <= 0.1):
                    err.append(f"metric {m['name']}: bound outside "
                               f"0.01..0.1")
            else:
                _line(m["layer"], f"metric {m['name']} layer", err)
                if m["moves"] not in {x.get("name") for x in e2e}:
                    err.append(f"metric {m['name']}: moves an unknown "
                               f"end-to-end metric")
    if "setup_s" not in {m.get("name") for m in e2e}:
        err.append("end_to_end lacks setup_s")
    for w in cell_names:
        got = {m["name"] for m in metrics_for(manifest, "end_to_end", w)}
        if "setup_s" not in got or len(got) < 2:
            err.append(f"cell {w}: needs setup_s and one more end-to-end "
                       f"metric")
        if not metrics_for(manifest, "per_layer", w):
            err.append(f"cell {w}: no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        err.append("BENCHMARK.json over 64 KiB")
    return err
