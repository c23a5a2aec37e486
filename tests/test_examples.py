"""Smoke-run every example main as a subprocess (the user-facing surface).

The reference ships a runnable Train.scala per model (SURVEY §2.9); these
are their argparse analogs — a flag rename or API drift in any of them is a
user-visible break that unit tests don't see. Each runs 1 epoch on tiny
synthetic data on the CPU platform. ~30-60 s apiece (jit compiles).
"""

import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "examples")

# (relative script, extra args) — sizes chosen for fastest-possible compiles.
# The compile-heavy tail is marked `slow` (tier-1 runtime budget, ROADMAP):
# each slow-marked family keeps a cheap tier-1 representative here or in its
# unit suite; `pytest -m slow` runs the full sweep before a release.
_SLOW = pytest.mark.slow
CASES = [
    ("lenet/train.py", ["--synthetic-size", "64", "--batch-size", "32"]),
    pytest.param("alexnet/train.py",
                 ["--synthetic-size", "16", "--batch-size", "8",
                  "--class-num", "4"], marks=_SLOW),
    pytest.param("vgg/train.py",
                 ["--synthetic-size", "32", "--batch-size", "16"],
                 marks=_SLOW),
    ("resnet/train.py", ["--depth", "8", "--synthetic-size", "32",
                         "--batch-size", "16", "--n-devices", "2"]),
    pytest.param("resnet/train.py",
                 ["--dataset", "imagenet", "--depth", "18",
                  "--synthetic-size", "16", "--batch-size", "8",
                  "--image-size", "32", "--class-num", "4",
                  "--warmup-epochs", "0", "--n-devices", "2"], marks=_SLOW),
    pytest.param("inception/train.py",
                 ["--synthetic-size", "4", "--batch-size", "2",
                  "--n-devices", "2"], marks=_SLOW),
    ("autoencoder/train.py", ["--synthetic-size", "64", "--batch-size", "32"]),
    ("textclassification/train.py", ["--synthetic-size", "32",
                                     "--batch-size", "16"]),
    pytest.param("ptb/train.py",
                 ["--synthetic-size", "800", "--batch-size", "8",
                  "--vocab-size", "50", "--hidden-size", "16"], marks=_SLOW),
    ("ncf/train.py", ["--synthetic-size", "256", "--batch-size", "64"]),
    ("widedeep/train.py", ["--synthetic-size", "256", "--batch-size", "64"]),
    ("treelstm/train.py", ["--synthetic-size", "32", "--batch-size", "8"]),
    ("keras/train.py", ["--synthetic-size", "64", "--batch-size", "32"]),
    pytest.param("transformer/train.py",
                 ["--synthetic-size", "600", "--batch-size", "4",
                  "--vocab-size", "60", "--hidden-size", "16",
                  "--seq-len", "16", "--decode-len", "6"], marks=_SLOW),
    pytest.param("pipeline/train.py",
                 ["--synthetic-size", "800", "--batch-size", "8",
                  "--vocab-size", "32", "--hidden-size", "16",
                  "--seq-len", "8", "--n-stages", "2", "--dp", "2"],
                 marks=_SLOW),
    pytest.param("moe/train.py",
                 ["--synthetic-size", "800", "--batch-size", "8",
                  "--vocab-size", "32", "--hidden-size", "16",
                  "--seq-len", "8", "--n-experts", "4"], marks=_SLOW),
    pytest.param("longctx/train.py",
                 ["--synthetic-size", "800", "--batch-size", "8",
                  "--vocab-size", "32", "--hidden-size", "16",
                  "--seq-len", "16", "--sp", "4"], marks=_SLOW),
]


def _run(script, args, timeout=420):
    cmd = [sys.executable, os.path.join(EXAMPLES, script),
           "--max-epoch", "1", "--platform", "cpu", *args]
    # each example is a fresh process whose jit compiles dominate its
    # runtime; the optimizers' own Engine.ensure_compilation_cache() places
    # the persistent cache, so repeat suite runs hit it
    return subprocess.run(cmd, capture_output=True, text=True, timeout=timeout)


def _case_script(case) -> str:
    # plain (script, args) tuple or a slow-marked pytest.param wrapper
    return case.values[0] if hasattr(case, "values") else case[0]


@pytest.mark.parametrize("script,args", CASES,
                         ids=[f"{_case_script(c).split('/')[0]}{i}"
                              for i, c in enumerate(CASES)])
def test_example_main_runs(script, args):
    r = _run(script, args)
    assert r.returncode == 0, (r.stdout + r.stderr)[-1500:]


@pytest.mark.slow  # two subprocess compiles; lenet0 keeps the tier-1 smoke
def test_lenet_train_then_test_flow(tmp_path):
    """train.py --model-save + test.py --model: the reference Train/Test pair."""
    saved = str(tmp_path / "lenet.bigdl.npz")
    r = _run("lenet/train.py", ["--synthetic-size", "64", "--batch-size", "32",
                                "--model-save", saved])
    assert r.returncode == 0, (r.stdout + r.stderr)[-1500:]
    r2 = _run("lenet/test.py", ["--model", saved, "--synthetic-size", "64",
                                "--batch-size", "32"])
    assert r2.returncode == 0, (r2.stdout + r2.stderr)[-1500:]


def test_interop_import_example():
    # --platform cpu keeps the test hermetic: without it this was the one
    # example test that initialized the accelerator backend (round-4
    # verdict: it hung the suite for 8m20s when the chip was unreachable).
    cmd = [sys.executable, os.path.join(EXAMPLES, "interop", "import_models.py"),
           "--platform", "cpu"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (r.stdout + r.stderr)[-1500:]


@pytest.mark.slow  # test_models keeps maskrcnn inference in tier-1
def test_maskrcnn_infer_example():
    cmd = [sys.executable, os.path.join(EXAMPLES, "maskrcnn", "infer.py"),
           "--platform", "cpu", "--image-size", "64"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (r.stdout + r.stderr)[-1500:]
