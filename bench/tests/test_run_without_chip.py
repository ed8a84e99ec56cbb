"""bench/run.py refuses to measure anywhere but on a TPU, and without the
program beside it, exiting non-zero with no result on standard output."""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).parents[2]
ARGS = ["--workload", "bert-large.phase1", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def run(where):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=where,
                          env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu():
    out = run(ROOT)
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    out = run(tmp_path)
    assert out.returncode != 0 and out.stdout == ""
