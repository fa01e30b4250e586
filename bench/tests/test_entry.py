"""The entry point refuses to measure without the chip, and without the
program: it exits non-zero and prints no result line."""

import os
import shutil
import subprocess
import sys

from harness.spec import BENCH, ROOT

ARGS = ["--workload", "smollm-135m.chat", "--seed", "2147483659",
        "--seconds", "2"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_non_zero_with_no_result():
    res = _run(ROOT)
    assert res.returncode == 2
    assert res.stdout == ""
    assert "needs 1 TPU" in res.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(tmp_path)
    assert res.returncode != 0
    assert res.stdout == ""
