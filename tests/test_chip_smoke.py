"""``chip_smoke.py``'s phases (b)–(e) on the CPU at a tiny size.

The script's ``main`` refuses to run off the TPU; its phase functions do
not, so the served path, the in-process kill/resume byte equality, the
reference comparisons and the kernel check stay covered here.
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

TINY = {
    "n_classes": 4,
    "clients_per_class": 2,
    "train_per_client": 40,
    "test_per_client": 8,
    "dim": 8,
    "hidden": (16,),
    "m": 4,
    "n_local_steps": 3,
    "batch_size": 10,
}


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def runs(smoke, tmp_path_factory):
    from repro.fl.experiment import ExperimentSpec, build_dataset

    out = tmp_path_factory.mktemp("chip_smoke")
    ds = build_dataset(ExperimentSpec.from_dict(smoke.spec_dict(TINY)).data)
    served = smoke.phase_served(TINY, out, ds)
    sketched = smoke.phase_sketched(TINY, out, ds)
    return ds, served, sketched, out


def test_served_phase_checkpoints_and_resumes(smoke, runs):
    _, served, _, out = runs
    assert len(served.history.records) == smoke.ROUNDS
    assert np.isfinite(served.history.series("train_loss")).all()
    assert os.path.exists(served.checkpoint)
    saved = json.loads((out / "served_history.json").read_text())
    assert smoke.canonical_history(served.history) == json.dumps(
        [{**r, "plan_build_ms": -1.0} for r in saved]
    )


def test_sketched_phase_runs_the_sketched_store(smoke, runs):
    _, _, sketched, _ = runs
    assert sketched.store.shape == (8, smoke.SKETCH_DIM)
    assert sketched.spec.planner.mode == "async"


def test_reference_phase_within_tolerance(smoke, runs):
    ds, served, _, _ = runs
    errs = smoke.phase_reference(served, ds)
    assert set(errs) == {"distances", "srp", "round_params", "round_loss"}
    assert all(0.0 <= e <= smoke.TOL for e in errs.values())


def test_kernel_phase_rejects_kernels_lowered_against_the_platform(smoke, runs, monkeypatch):
    """Interpreted kernels pass on the CPU; a platform that claims compiled
    kernels while the similarity kernel lowers interpreted fails the phase."""
    import repro.kernels

    _, served, _, _ = runs
    smoke.phase_kernels(served)
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    with pytest.raises(AssertionError, match="similarity kernel: interpret mode False"):
        smoke.phase_kernels(served)


def test_device_phase_refuses_the_cpu(smoke):
    with pytest.raises(SystemExit, match="no TPU"):
        smoke.phase_device()


def test_check_close_rejects_errors_beyond_tolerance(smoke):
    assert smoke.check_close("same", [1.0, 2.0], [1.0, 2.0]) == 0.0
    with pytest.raises(AssertionError, match="exceeds"):
        smoke.check_close("off", [1.0, 2.1], [1.0, 2.0])
