"""A run whose timed path is broken underneath comes out not correct: once for
each fault a one-chip cell can have. (No cell exchanges data between chips.)"""
import functools

import numpy as np
import pytest

import fedbench_tiny as ft


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return ft.make_tree(tmp_path_factory.mktemp("bench"))


def test_the_sound_program_is_correct(tree):
    for cell in ("tiny-shards.md", "tiny-dirichlet.alg2-sync"):
        res = ft.run(tree, cell, seconds=0.3)
        assert res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ["tiny-shards.md", "tiny-dirichlet.alg2-sync"])
def test_a_round_that_returns_its_state_unchanged(tree, cell, monkeypatch):
    from repro.fl.engine import BatchedRoundEngine

    run_round = BatchedRoundEngine.run_round

    def unchanged(self, params, *a, **k):
        _, updates, losses = run_round(self, params, *a, **k)
        return params, updates, losses

    monkeypatch.setattr(BatchedRoundEngine, "run_round", unchanged)
    res = ft.run(tree, cell, seconds=0.3)
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_of_each_batch_left_out(tree, monkeypatch):
    import repro.fl.engine as engine

    step = engine.batched_round_step

    @functools.wraps(step)
    def half(params, x, y, slots, batch_idx, *a, **k):
        return step(params, x, y, slots, batch_idx[:, :, : batch_idx.shape[2] // 2], *a, **k)

    monkeypatch.setattr(engine, "batched_round_step", half)
    res = ft.run(tree, "tiny-shards.md", seconds=0.3)
    assert not res["correct"]


def test_a_drawn_client_altered_where_it_is_drawn(tree, monkeypatch):
    from repro.core.samplers.base import ClientSampler
    from repro.core.types import SampleResult

    draw = ClientSampler._draw_from_plan

    def altered(self, plan, available=None):
        res = draw(self, plan, available)
        clients = res.clients.copy()
        clients[0] = (clients[0] + 1) % self.population.n_clients
        weights = np.bincount(clients, minlength=self.population.n_clients) / plan.m
        return SampleResult(clients=clients, agg_weights=weights)

    monkeypatch.setattr(ClientSampler, "_draw_from_plan", altered)
    res = ft.run(tree, "tiny-shards.md", seconds=0.3)
    assert not res["correct"]
    assert res["checks"]["draw_mismatch"]["value"] >= 1


def test_an_accuracy_altered_where_it_is_measured(tree, monkeypatch):
    import repro.models.simple as simple

    accuracy = simple.accuracy
    monkeypatch.setattr(simple, "accuracy", lambda p, x, y: accuracy(p, x, y) + 0.1)
    res = ft.run(tree, "tiny-shards.md", seconds=0.3)
    assert not res["correct"]
    assert res["checks"]["acc_gap"]["value"] == pytest.approx(0.1, rel=1e-3)


def test_a_plan_built_from_a_wrong_clustering(tree, monkeypatch):
    """Ward run on the clients in reverse order: every plan still meets
    Proposition 1, but its groups are not the similarity clusters."""
    import repro.core.clustering.backends as backends

    ward_linkage = backends.ward_linkage
    monkeypatch.setattr(backends, "ward_linkage", lambda dist: ward_linkage(dist[::-1, ::-1]))
    res = ft.run(tree, "tiny-dirichlet.alg2-sync", seconds=0.3)
    assert not res["correct"]
    assert res["checks"]["plan_gap"]["value"] > 0
