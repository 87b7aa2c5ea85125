"""Mesh-sharded batched round engine vs the single-device path.

The ``--xla_force_host_platform_device_count`` flag must be set before jax
initializes and must not leak into the other tests, so the actual runs
happen in a subprocess (same pattern as test_sharding_lowering).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax

from repro.core import MDSampler
from repro.fl import FLConfig, FederatedServer, by_class_shards, flatten_params
from repro.models.simple import init_mlp
from repro.optim import sgd

ds = by_class_shards(dim=16, noise=0.8, train_per_client=60, test_per_client=10, seed=0)


def run(mesh_spec, seed=7):
    params = init_mlp((16, 32, 10), seed=1)
    cfg = FLConfig(
        n_rounds=3, n_local_steps=6, batch_size=32, seed=0,
        engine="batched", mesh_spec=mesh_spec,
    )
    srv = FederatedServer(
        ds, MDSampler(ds.population, 8, seed=seed), params, sgd(0.08), cfg
    )
    srv.run()
    staged = [a.sharding for a in (srv._x_test, srv._y_test)]
    return (
        np.asarray(flatten_params(srv.params)),
        srv.history.series("train_loss"),
        srv._engine.per_device_staged_bytes(),
        {"acc": srv.history.series("test_acc").tolist(),
         "replicated": all(s.is_fully_replicated for s in staged),
         "devices": [len(s.device_set) for s in staged]},
    )


p1, l1, b1, e1 = run(None)
p4, l4, b4, e4 = run("4x1")
pa, la, ba, ea = run("auto")

# the pod-scale LM round driver on the same host mesh: client axis sharded,
# params replicated over "data" (launch.fl_train's cross-silo layout)
import dataclasses
from repro.configs import get_config
from repro.core import Algorithm1Sampler, ClientPopulation
from repro.launch.fl_train import FLLMConfig, run_federated_lm
from repro.launch.mesh import make_host_mesh

lm = dataclasses.replace(
    get_config("qwen3-0.6b", reduced=True),
    d_model=64, vocab_size=128, n_heads=2, n_kv_heads=2, head_dim=32,
)
flc = FLLMConfig(
    n_clients=8, m=4, n_rounds=2, n_local_steps=2, local_batch=2, seq_len=16, lr=0.1
)
pop = ClientPopulation(np.full(flc.n_clients, 100))
lm_losses = run_federated_lm(
    lm, flc, Algorithm1Sampler(pop, flc.m, seed=0), mesh=make_host_mesh(4, 1)
)
try:  # m not a multiple of the data-parallel degree must fail fast
    run_federated_lm(
        lm, dataclasses.replace(flc, m=2),
        Algorithm1Sampler(pop, 2, seed=0), mesh=make_host_mesh(4, 1),
    )
    m_guard = False
except ValueError:
    m_guard = True

from repro.fl.engine import staged_bytes
from repro.launch.mesh import resolve_fl_mesh

est1 = staged_bytes(ds, 8, 6, 32)
est4 = staged_bytes(ds, 8, 6, 32, mesh=resolve_fl_mesh("4x1"))

print(json.dumps({
    "devices": jax.device_count(),
    "max_abs_params": float(np.max(np.abs(p1 - p4))),
    "scale": float(np.max(np.abs(p1))),
    "max_abs_loss": float(np.max(np.abs(l1 - l4))),
    "auto_matches": bool(np.allclose(p4, pa)),
    "bytes_unsharded": int(b1),
    "bytes_4x1": int(b4),
    "est_unsharded": int(est1),
    "est_4x1": int(est4),
    "lm_losses_finite": bool(np.isfinite(np.asarray(lm_losses)).all()),
    "lm_m_guard": m_guard,
    "eval_1": e1,
    "eval_4x1": e4,
}))
"""


@pytest.fixture(scope="module")
def sharded_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=560,
    )
    assert out.returncode == 0, f"sharded-engine subprocess failed:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_round_matches_single_device_to_fp32_tol(sharded_results):
    r = sharded_results
    assert r["devices"] == 4
    # same realized rounds, reduction order differs across devices -> fp32 tol
    assert r["max_abs_params"] <= 1e-5 + 1e-4 * r["scale"]
    assert r["max_abs_loss"] <= 1e-4


def test_auto_mesh_spec_uses_all_local_devices(sharded_results):
    assert sharded_results["auto_matches"]


def test_client_sharded_staging_shrinks_per_device_bytes(sharded_results):
    r = sharded_results
    # 100 clients over 4 data-parallel groups: each device pins 1/4 of the set
    assert r["bytes_4x1"] * 4 == r["bytes_unsharded"]
    # the planning estimate (staged_bytes) agrees with the mesh it plans for
    assert r["est_4x1"] * 4 == r["est_unsharded"]


def test_federated_lm_driver_runs_on_host_mesh(sharded_results):
    """launch.fl_train's driver trains with the client axis sharded, and
    rejects an m the data-parallel degree does not divide."""
    assert sharded_results["lm_losses_finite"]
    assert sharded_results["lm_m_guard"]


def test_mesh_keeps_a_replicated_test_set_with_one_device_accuracy(sharded_results):
    """On a 4x1 mesh the test set is staged once, whole on every device, and
    each round's accuracy is the one-device run's."""
    one, mesh = sharded_results["eval_1"], sharded_results["eval_4x1"]
    assert one["devices"] == [1, 1]
    assert mesh["replicated"] and mesh["devices"] == [4, 4]
    assert len(mesh["acc"]) == 3
    np.testing.assert_allclose(mesh["acc"], one["acc"], rtol=0, atol=1e-6)
