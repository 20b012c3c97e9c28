"""The documents point at what is in the checkout.

One case per document (README.md, MIGRATION.md, every docs/*.md): each
path it names in code (a back-quoted span or a fenced block) under
``scripts/``, ``horovod_tpu/``, ``benchmarks/``, ``tests/`` or
``examples/`` exists, and none names the second benchmark and the CPU
perf gate that PR 28 deleted. Bare file names and ``docs/...`` are not
checked: the documents cite the reference's ``docs/*.rst`` and run-time
artefacts such as ``kernel_autotune.json`` that way.

One case per document again: every ``HOROVOD_*`` variable it mentions is
one the program reads, so a deleted lever does not live on in a guide.

The two documents that describe the benchmark (README.md,
docs/benchmarks.md) are held to ``BENCHMARK.json``, which is read and
never edited: a case per cell (the document names it), a case per
end-to-end metric (one line gives it the manifest's bound and, where the
manifest narrows it to some cells, those cells), and neither quotes the
ledger, whose numbers a document cannot keep up with.
"""

import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIXES = ("scripts/", "horovod_tpu/", "benchmarks/", "tests/", "examples/")
PLACEHOLDER = set("<*{$")
GONE = ("bench.py", "perf_gate")
FENCED = re.compile(r"```.*?```", flags=re.S)

DOCUMENTS = ["README.md", "MIGRATION.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))


def _text(document):
    with open(os.path.join(ROOT, document)) as f:
        return f.read()


def named_paths(text):
    """Words of the document's code (fenced blocks and back-quoted spans)
    that start with one of PREFIXES, cut at the first ``:`` (a line number
    or a ``::test`` id) and stripped of trailing punctuation."""
    code = FENCED.findall(text) + re.findall(r"`([^`]+)`",
                                             FENCED.sub("", text))
    for chunk in code:
        for word in chunk.split():
            if not word.startswith(PREFIXES) or PLACEHOLDER & set(word):
                continue
            yield word.split(":", 1)[0].rstrip(".,;)'\"")


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_what_exists(document):
    text = _text(document)
    missing = sorted({p for p in named_paths(text)
                      if not os.path.exists(os.path.join(ROOT, p))})
    assert not missing, f"{document} names paths that do not exist: {missing}"
    gone = [name for name in GONE if name in text]
    assert not gone, f"{document} still names {gone}"


# -- every HOROVOD_* variable a document mentions is read by the program --

VARIABLE = re.compile(r"HOROVOD_[A-Z0-9_]+\*?")
SOURCES = ("horovod_tpu", "scripts", "chip_smoke.py")


@pytest.fixture(scope="module")
def source_variables():
    names = set()
    for top in SOURCES:
        top = os.path.join(ROOT, top)
        files = [top] if os.path.isfile(top) else [
            os.path.join(d, f) for d, _, fs in os.walk(top)
            if "__pycache__" not in d and "/build" not in d for f in fs]
        for path in files:
            with open(path, errors="ignore") as f:
                names.update(m.rstrip("*") for m in VARIABLE.findall(f.read()))
    return names


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_variables_the_code_reads(document, source_variables):
    """A name that ends in ``_`` or is followed by ``*`` stands for a
    family (``HOROVOD_ELASTIC_*``): it must start a name the code reads."""
    mentioned = set(VARIABLE.findall(_text(document)))
    unknown = sorted(
        m for m in mentioned
        if not (any(n.startswith(m.rstrip("*")) for n in source_variables)
                if m.endswith(("_", "*")) else m in source_variables))
    assert not unknown, (
        f"{document} names variables no file under {SOURCES} reads: "
        f"{unknown}")


# -- README.md and docs/benchmarks.md against BENCHMARK.json --------------

BENCHMARK_DOCUMENTS = ["README.md", "docs/benchmarks.md"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
END_TO_END = {m["name"]: m for m in MANIFEST["end_to_end"]}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("document", BENCHMARK_DOCUMENTS)
def test_document_names_every_cell(document, cell):
    assert f"`{cell}`" in _text(document), (
        f"{document} does not name the cell {cell} of BENCHMARK.json")


@pytest.mark.parametrize("metric", sorted(END_TO_END))
@pytest.mark.parametrize("document", BENCHMARK_DOCUMENTS)
def test_document_gives_each_metric_its_bound(document, metric):
    """One line of the document holds the metric's name, the bound as
    the manifest writes it and, where the manifest lists ``workloads``,
    each of those cells."""
    want = [f"`{metric}`", str(END_TO_END[metric]["bound"])] + [
        f"`{c}`" for c in END_TO_END[metric].get("workloads", ())]
    lines = [ln for ln in _text(document).splitlines()
             if f"`{metric}`" in ln]
    assert any(all(w in re.split(r"[\s|,]+", ln) for w in want)
               for ln in lines), (
        f"{document}: no line gives {metric} as {want}; its lines: {lines}")


@pytest.mark.parametrize("document", BENCHMARK_DOCUMENTS)
def test_document_quotes_no_ledger_number(document):
    quoted = re.findall(r"\(ledger, PR \d+[^)]*\)", _text(document))
    assert not quoted, (
        f"{document} quotes the ledger {quoted}: point at "
        f"PERF_LEDGER.jsonl and PERF.md instead")
