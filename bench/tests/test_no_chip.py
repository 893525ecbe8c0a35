"""Without a TPU the benchmark exits non-zero and prints no result; so it
does in a directory holding only BENCHMARK.json and the benchmark."""

import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

ARGS = ["--workload", "qwen2.5-3b.train.silo3", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert "correct" not in p.stdout
