"""Batched round engine vs the reference per-client loop, plus its failure modes."""
import numpy as np
import pytest

from repro.core import Algorithm1Sampler, MDSampler
from repro.core.samplers.base import ClientSampler
from repro.core.types import SampleResult
from repro.fl import (
    BatchedRoundEngine,
    EmptyRoundError,
    FederatedServer,
    FLConfig,
    by_class_shards,
    flatten_params,
)
from repro.models.simple import fedprox_loss, init_mlp
from repro.optim import sgd


@pytest.fixture(scope="module")
def dataset():
    return by_class_shards(dim=16, noise=0.8, train_per_client=60, test_per_client=10, seed=0)


def _server(dataset, sampler, engine, *, rounds=4, mu=0.0, seed=0):
    params = init_mlp((16, 32, 10), seed=1)
    cfg = FLConfig(
        n_rounds=rounds, n_local_steps=8, batch_size=32,
        seed=seed, fedprox_mu=mu, engine=engine,
    )
    kw = {"loss_fn": fedprox_loss} if mu else {}
    return FederatedServer(dataset, sampler, params, sgd(0.08), cfg, **kw)


@pytest.mark.parametrize("mu", [0.0, 0.1], ids=["plain", "fedprox"])
@pytest.mark.parametrize("cls", [MDSampler, Algorithm1Sampler])
def test_batched_matches_reference(dataset, cls, mu):
    """Same sampler + server seed ⇒ identical realized rounds on both
    engines; final params must agree within fp32 tolerance."""
    pop = dataset.population
    runs = {}
    for engine in ("batched", "reference"):
        srv = _server(dataset, cls(pop, 10, seed=7), engine, mu=mu)
        srv.run()
        runs[engine] = srv
    fa = np.asarray(flatten_params(runs["batched"].params))
    fb = np.asarray(flatten_params(runs["reference"].params))
    np.testing.assert_allclose(fa, fb, rtol=1e-4, atol=1e-5)
    la = np.array(runs["batched"].history.series("train_loss"))
    lb = np.array(runs["reference"].history.series("train_loss"))
    np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-6)


def test_batched_handles_stale_mass(dataset):
    """Biased (uniform) sampling routes eq. 3's stale mass through the
    engine's on-device aggregation."""
    from repro.core import SAMPLERS

    pop = dataset.population
    a = _server(dataset, SAMPLERS["uniform"](pop, 10, seed=3), "batched", rounds=3)
    b = _server(dataset, SAMPLERS["uniform"](pop, 10, seed=3), "reference", rounds=3)
    a.run(), b.run()
    np.testing.assert_allclose(
        np.asarray(flatten_params(a.params)),
        np.asarray(flatten_params(b.params)),
        rtol=1e-4, atol=1e-5,
    )


class _EmptySampler(ClientSampler):
    """Degenerate sampler: never selects anyone."""

    def sample(self, round_idx):
        del round_idx
        n = self.population.n_clients
        return SampleResult(
            clients=np.array([], dtype=np.int64), agg_weights=np.zeros(n)
        )


@pytest.mark.parametrize("engine", ["batched", "reference"])
def test_zero_distinct_clients_raises_clearly(dataset, engine):
    srv = _server(dataset, _EmptySampler(dataset.population, 10), engine, rounds=1)
    with pytest.raises(EmptyRoundError, match="zero\\s+distinct clients"):
        srv.run_round(0)


def test_engine_rejects_overfull_round(dataset):
    eng = BatchedRoundEngine(dataset, m_slots=2, n_steps=2, batch_size=8)
    params = init_mlp((16, 32, 10), seed=1)
    from repro.models.simple import classification_loss

    with pytest.raises(ValueError, match="3 distinct clients for 2 slots"):
        eng.run_round(
            params, np.arange(3), np.full(3, 1 / 3), 0.0,
            np.random.default_rng(0), classification_loss, sgd(0.1),
        )


def test_engine_pads_heterogeneous_client_sizes():
    """Clients of different sizes stack into one padded block; padded rows
    are never drawn, so results stay finite and aggregation exact."""
    from repro.fl import dirichlet_labels

    ds = dirichlet_labels(alpha=1.0, dim=8, seed=0)
    sizes = {c.n_train for c in ds.clients}
    assert len(sizes) > 1  # the paper's CIFAR profile is genuinely unbalanced
    params = init_mlp((8, 16, 10), seed=1)
    cfg = FLConfig(n_rounds=2, n_local_steps=4, batch_size=16, seed=0, engine="batched")
    server = FederatedServer(ds, MDSampler(ds.population, 8, seed=1), params, sgd(0.05), cfg)
    hist = server.run()
    assert np.isfinite(hist.series("train_loss")).all()


def test_fl_engine_step_lowers_from_specs():
    """The launch-layer specs lower the batched round with zero allocation."""
    import jax

    from repro.launch.steps import fl_engine_input_specs, make_fl_engine_step
    from repro.models.simple import classification_loss

    specs = fl_engine_input_specs(
        n_clients=8, m_slots=4, n_pad=20, feat_shape=16, n_steps=3, batch_size=8
    )
    step = make_fl_engine_step(classification_loss, sgd(0.1))
    params = init_mlp((16, 32, 10), seed=0)
    new_params, updates, losses = jax.eval_shape(step, params, specs)
    d = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
    assert updates.shape == (4, d)
    assert losses.shape == (4,)
    assert jax.tree_util.tree_structure(new_params) == jax.tree_util.tree_structure(params)


def _image_loss(params, x, y, *prox_args):
    """Flatten image-shaped features before the MLP (CIFAR-style clients)."""
    from repro.models.simple import classification_loss, fedprox_loss

    flat = x.reshape(x.shape[0], -1)
    if prox_args:
        return fedprox_loss(params, flat, y, *prox_args)
    return classification_loss(params, flat, y)


def test_fl_engine_step_lowers_image_shaped_clients():
    """Tuple feat_shape: (H, W, C) clients lower through the same hooks."""
    import jax

    from repro.launch.steps import fl_engine_input_specs, make_fl_engine_step

    specs = fl_engine_input_specs(
        n_clients=6, m_slots=4, n_pad=12, feat_shape=(4, 4, 3), n_steps=2, batch_size=6
    )
    assert specs["x_all"].shape == (6, 12, 4, 4, 3)
    step = make_fl_engine_step(_image_loss, sgd(0.1))
    params = init_mlp((48, 24, 10), seed=0)
    new_params, updates, losses = jax.eval_shape(step, params, specs)
    d = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
    assert updates.shape == (4, d)
    assert losses.shape == (4,)
    del new_params


def test_engine_stages_native_dtypes():
    """The engine stages narrow dtypes as they are (an int8 label stays one
    byte), and ``per_device_staged_bytes`` counts exactly what it staged."""
    from repro.data.federated import ClientData, FederatedDataset

    rng = np.random.default_rng(0)
    clients = [
        ClientData(
            x_train=rng.normal(size=(30, 8)).astype(np.float32),
            y_train=rng.integers(0, 10, size=30).astype(np.int8),
            x_test=np.zeros((2, 8), np.float32),
            y_test=np.zeros(2, np.int8),
        )
        for _ in range(4)
    ]
    ds = FederatedDataset(clients)
    eng = BatchedRoundEngine(ds, m_slots=3, n_steps=5, batch_size=7)
    # 4 clients x 30 rows x (8 f32 features + 1 int8 label)
    assert eng.per_device_staged_bytes() == 4 * 30 * (8 * 4 + 1)
    assert eng._y_all.dtype == np.int8


class _ZeroWeightSampler(ClientSampler):
    """Degenerate sampler: selects clients but gives them zero weight."""

    def sample(self, round_idx):
        del round_idx
        n = self.population.n_clients
        return SampleResult(
            clients=np.arange(3, dtype=np.int64), agg_weights=np.zeros(n)
        )


@pytest.mark.parametrize("engine", ["batched", "reference"])
def test_zero_realized_weight_raises_instead_of_nan_loss(dataset, engine):
    """A round whose realized weights sum to 0 must fail loudly, not log a
    silent NaN train_loss (0/0 in the weighted average)."""
    srv = _server(dataset, _ZeroWeightSampler(dataset.population, 10), engine, rounds=1)
    with pytest.raises(EmptyRoundError, match="sum to zero"):
        srv.run_round(0)
    assert len(srv.history.records) == 0


@pytest.mark.parametrize("name", ["turbo", "compat"])
def test_unknown_engine_rejected(dataset, name):
    """``compat`` is no engine name: the per-client loop is the tests'
    reference, registered by the tests alone."""
    params = init_mlp((16, 32, 10), seed=1)
    cfg = FLConfig(n_rounds=1, engine=name)
    with pytest.raises(ValueError, match="unknown engine"):
        FederatedServer(dataset, MDSampler(dataset.population, 10), params, sgd(0.1), cfg)
