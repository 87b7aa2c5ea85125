"""CLI driver integration smoke: train / serve / report run end to end."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, timeout=520):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=timeout,
    )


def test_train_driver_reduces_loss(tmp_path):
    ckpt = os.path.join(tmp_path, "state.npz")
    out = _run(
        [
            "-m", "repro.launch.train", "--arch", "qwen3-0.6b", "--reduced",
            "--steps", "12", "--batch", "2", "--seq", "32", "--lr", "5e-3",
            "--checkpoint", ckpt,
        ]
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "improved: True" in out.stdout
    assert os.path.exists(ckpt)


def test_serve_driver_generates():
    out = _run(
        [
            "-m", "repro.launch.serve", "--arch", "qwen2-1.5b", "--reduced",
            "--batch", "2", "--prompt-len", "8", "--gen", "4",
        ]
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "decoded" in out.stdout


def test_report_renders_tables():
    if not os.path.isdir(os.path.join(ROOT, "experiments", "dryrun")):
        pytest.skip("dry-run artifacts absent")
    out = _run(["-m", "repro.launch.report"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "§Roofline" in out.stdout


def test_example_quickstart_runs():
    out = _run(["examples/sampling_statistics.py", "--sizes", "50", "50", "100", "--m", "2"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "Var ratio" in out.stdout


def test_compile_cache_honours_the_environment(monkeypatch):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert enable_compile_cache() == "/elsewhere/cache"
    assert calls == []  # JAX reads the variable itself; no other dir is set


def test_compile_cache_defaults_to_one_checkout_path(monkeypatch):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = enable_compile_cache(), enable_compile_cache()
    assert first == second == os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
