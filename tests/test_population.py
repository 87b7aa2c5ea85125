"""Population processes + the server's availability/drop-resolution phases."""
import numpy as np
import pytest

from repro.core import MDSampler
from repro.core.samplers.base import ClientSampler
from repro.core.types import SampleResult
from repro.fl import (
    POPULATIONS,
    BernoulliDropoutPopulation,
    EmptyRoundError,
    FederatedServer,
    FLConfig,
    PeriodicAvailabilityPopulation,
    PoissonChurnPopulation,
    PopulationProcess,
    StaticPopulation,
    build_population,
    by_class_shards,
    flatten_params,
)
from repro.models.simple import init_mlp
from repro.optim import sgd

N = 40


# --------------------------------------------------------------------------
# process semantics
# --------------------------------------------------------------------------
def test_registry_has_seed_scenarios():
    for name in ("static", "poisson", "periodic", "dropout"):
        assert name in POPULATIONS


def test_build_population_rejects_unknown_options():
    with pytest.raises(ValueError, match="does not accept option"):
        build_population({"name": "poisson", "options": {"jion_rate": 1.0}}, N)


def test_static_all_available_no_drops():
    pop = StaticPopulation(N)
    for t in (0, 3, 100):
        assert pop.available_mask(t).all()
        assert not pop.dropout_mask(t, np.arange(N)).any()


def test_masks_deterministic_in_seed_and_round():
    """The determinism contract: a mask is a pure function of (seed, t) —
    a second instance (a resumed server) replays the identical trajectory."""
    for cls, kw in (
        (PoissonChurnPopulation, dict(join_rate=0.3, leave_rate=0.4)),
        (PeriodicAvailabilityPopulation, dict(period=5, duty=0.4, stagger=False)),
        (BernoulliDropoutPopulation, dict(rate=0.3, straggle_rate=0.1)),
    ):
        a = cls(N, seed=7, **kw)
        b = cls(N, seed=7, **kw)
        ids = np.arange(N)
        # query b out of order / from the middle — replay must not care
        for t in (5, 0, 9, 2):
            np.testing.assert_array_equal(a.available_mask(t), b.available_mask(t))
            np.testing.assert_array_equal(a.dropout_mask(t, ids), b.dropout_mask(t, ids))
        c = cls(N, seed=8, **kw)
        assert any(
            not np.array_equal(a.available_mask(t), c.available_mask(t))
            or not np.array_equal(a.dropout_mask(t, ids), c.dropout_mask(t, ids))
            for t in range(10)
        )


def test_dropout_fate_independent_of_sampled_set():
    """A client's mid-round fate is keyed by its id, not by who else was
    drawn — the same client has the same fate under any co-sample."""
    pop = BernoulliDropoutPopulation(N, seed=3, rate=0.5)
    full = pop.dropout_mask(4, np.arange(N))
    subset = np.array([3, 17, 29])
    np.testing.assert_array_equal(pop.dropout_mask(4, subset), full[subset])


def test_poisson_churn_rates_move_the_mean():
    heavy = PoissonChurnPopulation(N, seed=0, join_rate=0.05, leave_rate=1.0)
    light = PoissonChurnPopulation(N, seed=0, join_rate=1.0, leave_rate=0.05)
    mh = np.mean([heavy.available_mask(t).mean() for t in range(30, 60)])
    ml = np.mean([light.available_mask(t).mean() for t in range(30, 60)])
    assert mh < 0.5 < ml


def test_poisson_min_available_floor():
    pop = PoissonChurnPopulation(N, seed=0, join_rate=0.0, leave_rate=5.0, min_available=3)
    for t in range(20):
        assert pop.available_mask(t).sum() >= 3


def test_periodic_windows_and_floor():
    pop = PeriodicAvailabilityPopulation(N, period=4, duty=0.5, stagger=True)
    masks = np.stack([pop.available_mask(t) for t in range(8)])
    # staggered phases: every round keeps roughly duty * n clients online
    assert (masks.sum(axis=1) >= 1).all()
    # period-4: the pattern repeats exactly
    np.testing.assert_array_equal(masks[:4], masks[4:])
    # degenerate duty with random phases still respects the floor
    tight = PeriodicAvailabilityPopulation(
        6, period=100, duty=0.01, stagger=False, min_available=2, seed=1
    )
    for t in range(10):
        assert tight.available_mask(t).sum() >= 2


def test_invalid_parameters_rejected():
    with pytest.raises(ValueError, match="drop_rate"):
        StaticPopulation(N, drop_rate=1.5)
    with pytest.raises(ValueError, match="min_available"):
        PoissonChurnPopulation(N, min_available=N + 1)
    with pytest.raises(ValueError, match="duty"):
        PeriodicAvailabilityPopulation(N, duty=0.0)
    with pytest.raises(ValueError, match="period"):
        PeriodicAvailabilityPopulation(N, period=0)


# --------------------------------------------------------------------------
# server integration: availability + degraded rounds
# --------------------------------------------------------------------------
class _ForcedDropPopulation(PopulationProcess):
    """Full availability; a fixed set of client ids always drops mid-round."""

    def __init__(self, n_clients, drop_ids=()):
        super().__init__(n_clients)
        self._drop = np.zeros(n_clients, dtype=bool)
        self._drop[list(drop_ids)] = True

    def _availability(self, t):
        return np.ones(self.n_clients, dtype=bool)

    def dropout_mask(self, t, client_ids):
        return self._drop[np.asarray(client_ids, dtype=np.int64)]


@pytest.fixture(scope="module")
def dataset():
    return by_class_shards(dim=16, noise=0.8, train_per_client=60, test_per_client=10, seed=0)


def _server(dataset, *, population, engine="batched", rounds=2, seed=0, m=10):
    params = init_mlp((16, 32, 10), seed=1)
    cfg = FLConfig(n_rounds=rounds, n_local_steps=4, batch_size=16, seed=seed, engine=engine)
    return FederatedServer(
        dataset, MDSampler(dataset.population, m, seed=seed), params, sgd(0.08), cfg,
        population=population,
    )


@pytest.mark.parametrize("engine", ["batched", "reference"])
def test_degraded_round_aggregates_survivors_only(dataset, engine):
    """Mid-round dropout with >= 1 live client: the dropped participants'
    weight is zeroed (their mass goes stale — the global model keeps it),
    telemetry reports the round as degraded, and the result equals a round
    where the same weights were zero from the start."""
    n = dataset.n_clients
    srv = _server(dataset, population=None, engine=engine, rounds=1)
    rec_full = srv.run_round(0)
    drawn = np.flatnonzero(rec_full.agg_weights)
    victim = int(drawn[0])

    a = _server(dataset, population=_ForcedDropPopulation(n, [victim]), engine=engine, rounds=1)
    rec = a.run_round(0)
    assert rec.round_status == "degraded"
    assert rec.n_dropped == 1
    assert rec.n_available == n
    assert rec.agg_weights[victim] == 0.0
    assert np.isfinite(rec.train_loss)

    # reference: a sampler that hands the server the already-zeroed weights
    # with the dropped mass pre-routed to the stale term
    w = np.array(rec_full.agg_weights, copy=True)
    stale = float(w[victim])
    w[victim] = 0.0

    class _Fixed(ClientSampler):
        def sample(self, round_idx, available=None):
            return SampleResult(
                clients=np.repeat(drawn, 1), agg_weights=w, stale_weight=stale
            )

    b = _server(dataset, population=None, engine=engine, rounds=1)
    b.sampler = _Fixed(dataset.population, 10, seed=0)
    rec_b = b.run_round(0)
    np.testing.assert_allclose(
        np.asarray(flatten_params(a.params)),
        np.asarray(flatten_params(b.params)),
        rtol=1e-5, atol=1e-6,
    )
    assert rec.train_loss == pytest.approx(rec_b.train_loss, rel=1e-5)


@pytest.mark.parametrize("engine", ["batched", "reference"])
def test_all_dropped_raises_empty_round_with_index(dataset, engine):
    """Every realized participant dropping is an EmptyRoundError naming the
    round — all realized aggregation mass is gone."""
    n = dataset.n_clients
    srv = _server(
        dataset, population=_ForcedDropPopulation(n, range(n)), engine=engine, rounds=1
    )
    with pytest.raises(EmptyRoundError, match=r"round 0.*dropped"):
        srv.run_round(0)
    assert len(srv.history.records) == 0


def test_nobody_available_raises_empty_round(dataset):
    class _Offline(PopulationProcess):
        def _availability(self, t):
            return np.zeros(self.n_clients, dtype=bool)

    srv = _server(dataset, population=_Offline(dataset.n_clients), rounds=1)
    with pytest.raises(EmptyRoundError, match="round 0.*zero"):
        srv.run_round(0)


def test_run_skip_empty_rides_out_dead_rounds(dataset):
    """skip_empty=True records empty placeholder rounds instead of raising;
    live rounds still train."""

    class _Blinking(PopulationProcess):
        def _availability(self, t):
            on = np.zeros(self.n_clients, dtype=bool)
            if t % 2 == 0:
                on[:] = True
            return on

    srv = _server(dataset, population=_Blinking(dataset.n_clients), rounds=4)
    hist = srv.run(skip_empty=True)
    status = [r.round_status for r in hist.records]
    assert status == ["ok", "empty", "ok", "empty"]
    empty = hist.records[1]
    assert empty.n_distinct_clients == 0 and np.isnan(empty.train_loss)
    assert empty.n_available == 0


def test_static_population_matches_no_population(dataset):
    """An attached all-available process must not perturb the numerics: the
    masked draw degenerates to the unconditional one bit-for-bit."""
    a = _server(dataset, population=None, rounds=3)
    b = _server(dataset, population=StaticPopulation(dataset.n_clients), rounds=3)
    ha, hb = a.run(), b.run()
    for ra, rb in zip(ha.records, hb.records):
        assert ra.train_loss == rb.train_loss
        np.testing.assert_array_equal(ra.agg_weights, rb.agg_weights)
        assert (ra.n_available, rb.n_available) == (-1, dataset.n_clients)


def test_availability_restricts_draws(dataset):
    """No draw ever lands on an unavailable client, and the realized weights
    re-normalize to 1 over the available set."""
    n = dataset.n_clients

    class _HalfOn(PopulationProcess):
        def _availability(self, t):
            mask = np.zeros(self.n_clients, dtype=bool)
            mask[: self.n_clients // 2] = True
            return mask

    srv = _server(dataset, population=_HalfOn(n), rounds=3)
    hist = srv.run()
    for rec in hist.records:
        assert rec.n_available == n // 2
        assert (rec.agg_weights[n // 2:] == 0).all()
        assert rec.agg_weights.sum() == pytest.approx(1.0)
