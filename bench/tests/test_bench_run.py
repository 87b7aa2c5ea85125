"""``bench/run.py`` refuses to run without a TPU, and without the program."""
import json
import os
import shutil
import subprocess
import sys

import fedbench_tiny as ft

ARGS = ["--workload", "fig1-mnist.md", "--seed", "5", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (json.JSONDecodeError, TypeError):
            continue
    return False


def test_fails_without_a_tpu():
    out = _run(ft.REPO)
    assert out.returncode != 0
    assert not _printed_result(out.stdout)
    assert "no TPU" in out.stderr and '"platform": "cpu"' in out.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ft.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(ft.BENCH_DIR, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert not _printed_result(out.stdout)
