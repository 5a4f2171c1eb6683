"""bench/run.py measures nothing without a TPU, and nothing in a
directory that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

import cellcheck

ROOT = cellcheck.ROOT
ARGS = ["--workload", "wt-history-w7d", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    run = _run(ROOT)
    assert run.returncode != 0
    assert '"correct"' not in run.stdout
    assert "needs a TPU" in run.stderr


def test_refuses_with_only_its_own_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in harness_paths():
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    run = _run(tmp_path)
    assert run.returncode != 0
    assert '"correct"' not in run.stdout


def harness_paths():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)["paths"]
