"""The documents point at what is in the checkout.

One case per document (README.md, MIGRATION.md, every docs/*.md): each
path it names in code (a back-quoted span or a fenced block) under
``scripts/``, ``horovod_tpu/``, ``benchmarks/``, ``tests/`` or
``examples/`` exists, and none names the second benchmark and the CPU
perf gate that PR 28 deleted. Bare file names and ``docs/...`` are not
checked: the documents cite the reference's ``docs/*.rst`` and run-time
artefacts such as ``kernel_autotune.json`` that way.
"""

import glob
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
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    missing = sorted({p for p in named_paths(text)
                      if not os.path.exists(os.path.join(ROOT, p))})
    assert not missing, f"{document} names paths that do not exist: {missing}"
    gone = [name for name in GONE if name in text]
    assert not gone, f"{document} still names {gone}"
