"""The benchmark refuses to run without a TPU, and in a directory that
holds only BENCHMARK.json and the benchmark's own files: it exits non-zero
and prints no result line."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "qwen2-0.5b.churn", "--seed", str(2 ** 33 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, tmp):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp),
               TMPDIR=str(tmp), JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result(tmp_path):
    p = _run(ROOT, tmp_path)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), alone)
    shutil.copytree(os.path.join(ROOT, "bench"), alone / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(alone), tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
