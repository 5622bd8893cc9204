"""Every file a document or a package module names is in the tree.

A pointer to a file that was deleted, or never written, sends the reader
looking for a record that is not there.  Each document a user opens, and
each package directory's sources, is read for tokens of the form
``<top-level dir>/....<ext>`` and for the bare ``bench.py``; each must
name a file (a ``{a,b}`` group or a ``*`` glob: every alternative, at
least one match).  ``CHANGES.md``, ``ROADMAP.md`` and ``PERF.md`` tell
history, name what was deleted, and are not read.
"""

import glob
import itertools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ("README.md", "docs/DESIGN.md", "PARITY.md", "BASELINE.md",
             "native/README.md", "run-demo-local.sh", "run-demo-cluster.sh",
             "run-demo-tpu.sh")
PACKAGE_DIRS = ("cocoa_tpu", "cocoa_tpu/analysis", "cocoa_tpu/data",
                "cocoa_tpu/evals", "cocoa_tpu/ops", "cocoa_tpu/parallel",
                "cocoa_tpu/serving", "cocoa_tpu/solvers",
                "cocoa_tpu/telemetry", "cocoa_tpu/utils")
HISTORY = ("PERF.md", "ROADMAP.md", "CHANGES.md")

_PATH = re.compile(
    r"(?<![\w/.\-])"
    r"((?:cocoa_tpu|chipbench|benchmarks|tests|native|docs)/[\w./{},*\-]*"
    r"\.(?:py|md|jsonl|json|sh|cpp))\b"
    r"|(?<![\w/])(bench\.py)\b")
_GROUP = re.compile(r"\{([^{}]*)\}")


def _alternatives(token):
    """``a/{b,c}.py`` -> ``a/b.py``, ``a/c.py``."""
    groups = _GROUP.findall(token)
    if not groups:
        return [token]
    template = _GROUP.sub("{}", token)
    return [template.format(*pick) for pick in
            itertools.product(*(g.split(",") for g in groups))]


def _read(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


def _missing(text):
    out = set()
    for m in _PATH.finditer(text):
        for path in _alternatives(m.group(1) or m.group(2)):
            if not glob.glob(os.path.join(ROOT, path)):
                out.add(path)
    return sorted(out)


def _sources(target):
    path = os.path.join(ROOT, target)
    if os.path.isdir(path):
        return sorted(glob.glob(os.path.join(path, "*.py")))
    return [path]


@pytest.mark.parametrize("target", DOCUMENTS + PACKAGE_DIRS)
def test_named_files_exist(target):
    sources = _sources(target)
    assert sources, f"nothing to read at {target}"
    missing = {}
    for src in sources:
        gone = _missing(_read(src))
        if gone:
            missing[os.path.relpath(src, ROOT)] = gone
    assert not missing, f"names of files that are not in the tree: {missing}"


def test_no_generated_bench_block_outside_history():
    """Speeds live in PERF.md and PERF_LEDGER.jsonl; no other document
    carries a block stamped from a second record."""
    docs = [p for pat in ("*.md", "docs/*.md", "native/*.md")
            for p in glob.glob(os.path.join(ROOT, pat))
            if os.path.basename(p) not in HISTORY + ("ISSUE.md",)]
    assert docs
    marked = [os.path.relpath(p, ROOT) for p in docs
              if "GENERATED:bench" in _read(p)]
    assert not marked
