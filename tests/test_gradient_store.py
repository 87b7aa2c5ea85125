"""GradientStore semantics: duplicate-id pin, sketch stage, mesh, load.

The backend-parity contract: the jax scatter path and the numpy fallback
implement *one* semantics — last-write-wins on duplicate ids, ids >= n
dropped (padded-slot sentinels), negative-free (callers pass real or
sentinel ids only). The sketch stage compresses before scatter so the
resident buffer is (n, d'); ``sketch="identity"`` must be bit-for-bit the
unsketched store. ``load`` adopts device arrays without a host round-trip
(checked by identity), and restores through a mesh re-place the sharding.

The multi-device sharded path runs in a subprocess (the XLA host-device
flag must be set before jax initializes), same pattern as
``test_engine_sharded``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.fl.gradient_store import GradientStore

ROOT = os.path.join(os.path.dirname(__file__), "..")

BACKENDS = ["jax", "numpy"]


# --------------------------------------------------------------------------
# duplicate ids: one pinned semantics on both backends
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_duplicate_ids_last_write_wins(backend):
    store = GradientStore(5, 3, backend=backend)
    vals = np.stack([
        np.full(3, 1.0), np.full(3, 2.0), np.full(3, 3.0), np.full(3, 4.0),
    ]).astype(np.float32)
    store.update(np.array([2, 0, 2, 2]), vals)
    G = store.asnumpy()
    np.testing.assert_allclose(G[0], 2.0)
    np.testing.assert_allclose(G[2], 4.0)  # the LAST write to id 2
    np.testing.assert_allclose(G[[1, 3, 4]], 0.0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_duplicate_sentinels_with_real_rows(backend):
    """Duplicates of a padded sentinel (id >= n) stay dropped; the real
    rows among them still land."""
    store = GradientStore(4, 2, backend=backend)
    vals = np.stack([
        np.full(2, 9.0), np.full(2, 1.0), np.full(2, 9.0), np.full(2, 5.0),
    ]).astype(np.float32)
    store.update(np.array([4, 1, 4, 1]), vals)
    G = store.asnumpy()
    np.testing.assert_allclose(G[1], 5.0)
    assert not np.isin(9.0, G)


def test_backends_agree_on_update_sequence():
    """Same scatter sequence (dups, sentinels, decay) → identical buffers."""
    rng = np.random.default_rng(0)
    stores = {
        b: GradientStore(8, 5, staleness_decay=0.75, backend=b) for b in BACKENDS
    }
    for _ in range(4):
        ids = rng.integers(0, 10, size=6)  # includes 8, 9 sentinels + dups
        vals = rng.normal(size=(6, 5)).astype(np.float32)
        for st in stores.values():
            st.update(ids, vals)
    np.testing.assert_array_equal(stores["jax"].asnumpy(), stores["numpy"].asnumpy())


# --------------------------------------------------------------------------
# sketch stage
# --------------------------------------------------------------------------
def test_sketched_store_resident_shape_and_bytes():
    store = GradientStore(10, 256, sketch="srp", sketch_dim=16)
    assert store.dim == 16
    assert store.update_dim == 256
    assert store.nbytes == 10 * 16 * 4
    store.update(np.array([3]), np.ones((1, 256), np.float32))
    snap = np.asarray(store.snapshot())
    assert snap.shape == (10, 16)
    assert np.any(snap[3] != 0) and np.all(snap[[0, 1, 2, 4]] == 0)
    # update() still takes full-width rows — the wrong width is rejected
    with pytest.raises(ValueError, match="updates shape"):
        store.update(np.array([0]), np.ones((1, 16), np.float32))


def test_identity_sketch_is_bitwise_legacy_path():
    rng = np.random.default_rng(1)
    plain = GradientStore(6, 12)
    ident = GradientStore(6, 12, sketch="identity")
    assert ident.dim == 12 and ident.nbytes == plain.nbytes
    for _ in range(3):
        ids = rng.integers(0, 7, size=4)
        vals = rng.normal(size=(4, 12)).astype(np.float32)
        plain.update(ids, vals)
        ident.update(ids, vals)
    np.testing.assert_array_equal(plain.asnumpy(), ident.asnumpy())


@pytest.mark.parametrize("sketch", ["srp", "countsketch"])
def test_sketched_backends_agree(sketch):
    """numpy fallback (sketch.reference) tracks the device path closely."""
    rng = np.random.default_rng(2)
    ids = np.array([0, 2, 3])
    vals = rng.normal(size=(3, 200)).astype(np.float32)
    out = {}
    for b in BACKENDS:
        st = GradientStore(5, 200, sketch=sketch, sketch_dim=8, backend=b)
        st.update(ids, vals)
        out[b] = st.asnumpy()
    np.testing.assert_allclose(out["jax"], out["numpy"], rtol=1e-5, atol=1e-5)


def test_sketch_seed_changes_resident_rows():
    vals = np.ones((1, 64), np.float32)
    a = GradientStore(3, 64, sketch="srp", sketch_dim=8, sketch_seed=0)
    b = GradientStore(3, 64, sketch="srp", sketch_dim=8, sketch_seed=1)
    a.update([0], vals)
    b.update([0], vals)
    assert not np.allclose(a.asnumpy()[0], b.asnumpy()[0])


# --------------------------------------------------------------------------
# gather_rows
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_gather_rows_returns_requested_rows(backend):
    store = GradientStore(6, 4, backend=backend)
    vals = np.arange(12, dtype=np.float32).reshape(3, 4)
    store.update(np.array([1, 4, 5]), vals)
    rows = np.asarray(store.gather_rows(np.array([4, 1])))
    np.testing.assert_allclose(rows, vals[[1, 0]])


# --------------------------------------------------------------------------
# load: device adoption, dtype/shape validation
# --------------------------------------------------------------------------
def test_load_adopts_device_array_without_host_roundtrip():
    jnp = pytest.importorskip("jax.numpy")
    store = GradientStore(4, 3)
    G = jnp.full((4, 3), 2.5, jnp.float32)
    store.load(G)
    assert store.snapshot() is G  # adopted, not copied through host
    np.testing.assert_allclose(store.asnumpy(), 2.5)


def test_load_rejects_wrong_dtype_device_array():
    jnp = pytest.importorskip("jax.numpy")
    store = GradientStore(4, 3)
    # (f64 can't be exercised without the x64 flag — jax silently builds f32)
    with pytest.raises(ValueError, match="float32"):
        store.load(jnp.zeros((4, 3), jnp.int32))
    with pytest.raises(ValueError, match="float32"):
        store.load(jnp.zeros((4, 3), jnp.bfloat16))


@pytest.mark.parametrize("backend", BACKENDS)
def test_load_rejects_wrong_shape(backend):
    store = GradientStore(4, 3, backend=backend)
    with pytest.raises(ValueError, match="checkpointed G shape"):
        store.load(np.zeros((4, 5), np.float32))
    # sketched store checkpoints the (n, d') buffer, not (n, d)
    sk = GradientStore(4, 64, sketch="srp", sketch_dim=3, backend=backend)
    with pytest.raises(ValueError, match="checkpointed G shape"):
        sk.load(np.zeros((4, 64), np.float32))
    sk.load(np.zeros((4, 3), np.float32))


@pytest.mark.parametrize("backend", BACKENDS)
def test_load_casts_host_f64(backend):
    store = GradientStore(2, 2, backend=backend)
    store.load(np.full((2, 2), 1.5, np.float64))
    out = store.asnumpy()
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, 1.5)


# --------------------------------------------------------------------------
# mesh: single-device inline; 4-device parity in a subprocess
# --------------------------------------------------------------------------
def test_mesh_spec_single_device_matches_unsharded():
    pytest.importorskip("jax")
    rng = np.random.default_rng(3)
    plain = GradientStore(8, 6)
    meshed = GradientStore(8, 6, mesh_spec=(1, 1))
    for _ in range(2):
        ids = rng.integers(0, 9, size=5)
        vals = rng.normal(size=(5, 6)).astype(np.float32)
        plain.update(ids, vals)
        meshed.update(ids, vals)
    np.testing.assert_array_equal(plain.asnumpy(), meshed.asnumpy())
    np.testing.assert_array_equal(
        np.asarray(plain.gather_rows([2, 7])), np.asarray(meshed.gather_rows([2, 7]))
    )
    meshed.load(plain.asnumpy())
    np.testing.assert_array_equal(plain.asnumpy(), meshed.asnumpy())


def test_mesh_spec_rejected_on_numpy_backend():
    with pytest.raises(RuntimeError, match="mesh_spec"):
        GradientStore(4, 3, backend="numpy", mesh_spec=(1, 1))


SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax

from repro.fl.gradient_store import GradientStore

rng = np.random.default_rng(0)
n, d, dp = 8, 64, 16  # n divides the 4-way data axis -> client axis sharded
plain = GradientStore(n, d, sketch="srp", sketch_dim=dp)
shard = GradientStore(n, d, sketch="srp", sketch_dim=dp, mesh_spec="4x1")
for _ in range(3):
    ids = rng.integers(0, n + 2, size=5)
    vals = rng.normal(size=(5, d)).astype(np.float32)
    plain.update(ids, vals)
    shard.update(ids, vals)

G = shard.snapshot()
n_shards = len({str(s.index) for s in G.addressable_shards})
shard_rows = G.addressable_shards[0].data.shape[0]
rows = np.asarray(shard.gather_rows(np.array([1, 6])))
rows_plain = np.asarray(plain.gather_rows(np.array([1, 6])))

# restore through load(): device array adopted + re-placed on the mesh
shard2 = GradientStore(n, d, sketch="srp", sketch_dim=dp, mesh_spec="4x1")
shard2.load(G)

# replication fallback: n not divisible by the data degree still works
odd = GradientStore(n + 1, d, sketch="srp", sketch_dim=dp, mesh_spec="4x1")
odd.update(np.array([0]), np.ones((1, d), np.float32))

# the Pallas kernels on mesh-sharded inputs (a Mosaic kernel cannot be
# partitioned; call_on_mesh runs it replicated over the input's mesh)
from repro.core.clustering import pairwise_distances
from repro.kernels.similarity.ops import resolve_distance_backend

dist = resolve_distance_backend("auto", as_numpy=False)(G, "arccos")
X = jax.device_put(rng.normal(size=(n, d)).astype(np.float32), G.sharding)
sk = shard.sketch(X)

print(json.dumps({
    "mesh_kernels_match": bool(
        np.allclose(np.asarray(dist), pairwise_distances(np.asarray(G), "arccos"), atol=1e-5)
        and np.allclose(np.asarray(sk), shard.sketch.reference(np.asarray(X)), atol=1e-5)
    ),
    "mesh_kernels_replicated": [len(dist.sharding.device_set), len(sk.sharding.device_set),
                                dist.sharding.is_fully_replicated, sk.sharding.is_fully_replicated],
    "devices": jax.device_count(),
    "sharded_matches": bool(np.array_equal(plain.asnumpy(), shard.asnumpy())),
    "n_shards": n_shards,
    "shard_rows": shard_rows,
    "gather_matches": bool(np.array_equal(rows, rows_plain)),
    "load_matches": bool(np.array_equal(shard2.asnumpy(), shard.asnumpy())),
    "odd_row_set": bool(np.any(odd.asnumpy()[0] != 0)),
}))
"""


@pytest.fixture(scope="module")
def sharded_store_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT], capture_output=True, text=True,
        env=env, cwd=ROOT, timeout=560,
    )
    assert out.returncode == 0, f"sharded-store subprocess failed:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_kernels_run_on_mesh_sharded_inputs(sharded_store_results):
    """Distances over the sharded store and the SRP sketch of a sharded
    block match their references and come back replicated on the mesh."""
    r = sharded_store_results
    assert r["mesh_kernels_match"]
    assert r["mesh_kernels_replicated"] == [4, 4, True, True]


def test_sharded_store_matches_unsharded(sharded_store_results):
    r = sharded_store_results
    assert r["devices"] == 4
    assert r["sharded_matches"]
    assert r["n_shards"] == 4  # client axis genuinely split across devices
    assert r["shard_rows"] == 2  # 8 clients / 4-way data axis
    assert r["gather_matches"]
    assert r["load_matches"]
    assert r["odd_row_set"]


# --------------------------------------------------------------------------
# checkpoint meta: restoring across sketch identities fails loudly
# --------------------------------------------------------------------------
def _algo2(n=8, **kw):
    from repro.core.samplers.algorithm2 import Algorithm2Sampler
    from repro.core.types import ClientPopulation

    pop = ClientPopulation(np.full(n, 100))
    return Algorithm2Sampler(pop, 4, update_dim=32, seed=0, **kw)


def test_sampler_state_roundtrips_sketched_store():
    s = _algo2(sketch="srp", sketch_dim=8)
    s.sample(0)
    s.observe_updates(
        np.arange(4), np.random.default_rng(0).normal(size=(4, 32)).astype(np.float32)
    )
    meta, arrays = s.state_meta(), s.state_arrays()
    assert meta["sketch"] == "srp"
    assert meta["sketch_dim"] == 8
    assert meta["sketch_seed"] == 0  # rides the sampler seed
    assert arrays["store_G"].shape == (8, 8)
    t = _algo2(sketch="srp", sketch_dim=8)
    t.load_state(meta, arrays)
    np.testing.assert_array_equal(
        t.gradient_store.asnumpy(), s.gradient_store.asnumpy()
    )


def test_sampler_rejects_checkpoint_from_other_sketch():
    s = _algo2(sketch="srp", sketch_dim=8)
    s.sample(0)
    meta, arrays = s.state_meta(), s.state_arrays()
    for other in (
        _algo2(),                               # unsketched
        _algo2(sketch="countsketch", sketch_dim=8),  # different construction
        _algo2(sketch="srp", sketch_dim=16),    # different width
    ):
        other.sample(0)
        with pytest.raises(ValueError, match="sketch"):
            other.load_state(meta, arrays)


def test_unsketched_checkpoint_without_sketch_keys_still_loads():
    """Pre-sketch checkpoints (no sketch meta keys) restore into an
    unsketched store — forward compatibility for existing bundles."""
    s = _algo2()
    s.sample(0)
    meta, arrays = s.state_meta(), s.state_arrays()
    for k in ("sketch", "sketch_dim", "sketch_seed"):
        meta.pop(k, None)
    t = _algo2()
    t.sample(0)
    t.load_state(meta, arrays)
    np.testing.assert_array_equal(
        t.gradient_store.asnumpy(), s.gradient_store.asnumpy()
    )
