"""The documents name only what the tree holds.

PR 30 removed a second measurement system (``bench`` + its tools, records and
``BENCH_*`` environment names) that the documents went on teaching long after
nothing ran it, beside two artifact files that never existed. Each document
here is held to two rules so that cannot come back unseen: every repository
path it names or runs exists, and it names no ``BENCH_*`` environment
variable.

A path is: anything under one of the tree's directories, wherever it stands
in the text (fenced commands included); the script of a ``python <script>``
command; and a bare file name in backticks — any ``*.py``, and the
upper-case ``*.json`` / ``*.md`` names, which is how the root's records are
spelled (lower-case ``*.json`` names are files a run writes into a bundle or
an artifact directory, not repository paths). A bare name may be any file of
the tree, not only one at the root (``MANIFEST.json`` is a fixture's).
Placeholders (``<cell>``, ``*``, ``{a,b}``, ``$VAR``) are skipped.
"""

import functools
import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

DOCUMENTS = (
    "README.md",
    "docs/analysis.md",
    "docs/observability.md",
    "docs/parallelism.md",
    "docs/performance.md",
    "docs/resilience.md",
    "docs/serving.md",
    ".claude/skills/verify/SKILL.md",
    "tools/check.sh",
)

_DIRS = "tools|tests|benchmark|bench_artifacts|docs|examples|bigdl_tpu|csrc"
_IN_DIR = re.compile(r"(?<![\w/.<>-])(?:%s)/[^\s`'\"),;|\]]*" % _DIRS)
_BARE = re.compile(r"^(?:[A-Za-z_][\w.-]*\.py|[A-Z][\w.-]*\.(?:json|md))$")
_RUN = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
_PLACEHOLDER = re.compile(r"[<>*{}$…]")
_BENCH_ENV = re.compile(r"\bBENCH_[A-Z][A-Z_0-9]*")


@functools.lru_cache(maxsize=None)
def _tree_file_names():
    names = set()
    for root, dirs, files in os.walk(REPO):
        if Path(root) == REPO:
            # scratch, caches and what a chip call brought back are not the tree
            dirs[:] = [d for d in dirs
                       if d == ".claude" or not (d.startswith(".") or d == "chiprun_out")]
        names.update(files)
    return names


def named_paths(text):
    found = set(_IN_DIR.findall(text)) | set(_RUN.findall(text))
    for span in re.findall(r"`([^`\n]+)`", text):
        found.update(tok for tok in span.split() if _BARE.match(tok))
    paths = set()
    for raw in found:
        path = raw.split("::")[0].rstrip(".:")
        if not _PLACEHOLDER.search(path):
            paths.add(path)
    return paths


def test_the_scan_finds_what_it_is_for():
    env_name = "BENCH_" + "MODE"  # split: the tree is grepped for such names
    text = ("run `python gone.py` or\n```\npython tools/gone_tool.py --selftest\n```\n"
            "see `GONE_r03.json`, bench_artifacts/GONE_r01.json, "
            "`tests/test_gone.py::TestGone`, `benchmark/workloads/<cell>.json`, "
            "`<run_dir>/postmortem/hard_crash/context.json` and `MANIFEST.json`; "
            f"set {env_name}=serving, read BENCHMARK.json")
    assert named_paths(text) == {
        "gone.py", "tools/gone_tool.py", "GONE_r03.json",
        "bench_artifacts/GONE_r01.json", "tests/test_gone.py", "MANIFEST.json"}
    assert _BENCH_ENV.findall(text) == [env_name]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_the_tree_holds(document):
    text = (REPO / document).read_text()
    names = _tree_file_names()
    missing = sorted(
        p for p in named_paths(text)
        if not (REPO / p).exists() and not ("/" not in p and p in names))
    assert not missing, f"{document} names paths the tree does not hold: {missing}"
    env = sorted(set(_BENCH_ENV.findall(text)))
    assert not env, f"{document} names BENCH_* environment variables: {env}"
