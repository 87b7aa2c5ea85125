"""The round's profiler spans: names, nesting, step numbers and counters, and
a traced run that computes bit for bit what an untraced one computes."""
import glob
import json
import os

import jax
import numpy as np
import pytest

import repro.fl.engine as engine_mod
from repro.core import Algorithm2Sampler, MDSampler
from repro.core.samplers.base import ClientSampler
from repro.core.types import SampleResult
from repro.fl import EmptyRoundError, FederatedServer, FLConfig, by_class_shards
from repro.fl.aggregation import flatten_params
from repro.fl.population import PopulationProcess
from repro.fl.scheduler import DeadlineScheduler
from repro.models.simple import accuracy, init_mlp
from repro.optim import sgd

ROUNDS, M = 3, 6
#: Each span of a batched-engine round and the span it nests in.
PARENT = {
    "fl.availability": "fl.round",
    "fl.draw": "fl.round",
    "fl.resolve": "fl.round",
    "fl.local_work": "fl.round",
    "fl.local_work.prep": "fl.local_work",
    "fl.local_work.dispatch": "fl.local_work",
    "fl.local_work.wait": "fl.local_work",
    "fl.observe": "fl.round",
    "fl.observe.gather": "fl.observe",
    "fl.eval": "fl.round",
    "fl.eval.run": "fl.eval",
    "fl.record": "fl.round",
}
#: Spans that open only in a round where a participant dropped or was late.
DEGRADED_ONLY = {"fl.observe.gather"}
ENGINES = ["batched", "compat"]
#: The client that :class:`_DropOne` drops whenever it is drawn.
DROPPED = 2


@pytest.fixture(scope="module")
def dataset():
    return by_class_shards(dim=16, noise=0.8, train_per_client=40, test_per_client=10, seed=0)


def _server(dataset, engine="batched", sampler=None, **kwargs):
    cfg = FLConfig(n_rounds=ROUNDS, n_local_steps=3, batch_size=8, seed=5, engine=engine)
    sampler = sampler or MDSampler(dataset.population, M, seed=11)
    return FederatedServer(
        dataset, sampler, init_mlp((16, 16, 10), seed=1), sgd(0.05), cfg, **kwargs
    )


class _FixedSampler(ClientSampler):
    """Draws clients ``0..M-1`` in every round, each with weight 1/M."""

    def sample(self, round_idx, available=None):
        weights = np.zeros(self.population.n_clients)
        weights[:M] = 1.0 / M
        return SampleResult(clients=np.arange(M, dtype=np.int64), agg_weights=weights)


class _DropOne(PopulationProcess):
    """Everyone is available; client ``DROPPED`` vanishes mid-round whenever drawn."""

    def _availability(self, t):
        return np.ones(self.n_clients, dtype=bool)

    def dropout_mask(self, t, client_ids):
        return np.asarray(client_ids) == DROPPED


def _degraded_server(dataset, engine, cause):
    """A fixed draw in which someone drops (``"drop"``) or misses the
    deadline (``"late"``)."""
    n = dataset.population.n_clients
    kwargs = (
        {"population": _DropOne(n)}
        if cause == "drop"
        else {"scheduler": DeadlineScheduler(n, M, seed=3, straggle_frac=0.5)}
    )
    return _server(dataset, engine, _FixedSampler(dataset.population, M), **kwargs)


def _recording(srv):
    """Record each round's distinct clients and the engine's rows, and each
    ``observe_updates`` call as ``(round, ids, rows)``."""
    rounds, observed = [], []
    local_work, observe = srv._phase_local_work, srv.sampler.observe_updates

    def recording_local_work(distinct, *args):
        out = local_work(distinct, *args)
        rounds.append((np.asarray(distinct), out[1]))
        return out

    def recording_observe(ids, rows):
        observed.append((len(rounds) - 1, np.asarray(ids), rows))
        return observe(ids, rows)

    srv._phase_local_work = recording_local_work
    srv.sampler.observe_updates = recording_observe
    return rounds, observed


def _fl_events(log_dir):
    """``(name, start, end, arguments)`` of every ``fl.*`` host event, by start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns, {k: v for k, v in e.stats})
                    for e in line.events
                    if e.name.startswith("fl.")
                )
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _traced_run(srv, log_dir):
    with jax.profiler.trace(str(log_dir)):
        history = srv.run()
        jax.block_until_ready(srv.params)
    return history, _fl_events(str(log_dir))


def _canon_json(history) -> str:
    """History JSON with wall-clock telemetry (plan_build_ms) normalized."""
    recs = json.loads(history.to_json())
    for r in recs:
        r["plan_build_ms"] = -1.0
    return json.dumps(recs)


def _enclosing(events, child, name):
    """The ``name`` events that enclose ``child``."""
    _, start, end, _ = child
    return [e for e in events if e[0] == name and e[1] <= start and end <= e[2]]


@pytest.fixture(scope="module")
def traced(dataset, tmp_path_factory):
    """A batched-engine run under a trace, with the device bytes each round
    actually moved: the step's four per-round inputs."""
    dispatched = []
    step = engine_mod.batched_round_step

    def recording_step(*args, **kwargs):
        dispatched.append(sum(a.nbytes for a in args[3:7]))
        return step(*args, **kwargs)

    srv = _server(dataset)
    engine_mod.batched_round_step = recording_step
    try:
        history, events = _traced_run(srv, tmp_path_factory.mktemp("trace"))
    finally:
        engine_mod.batched_round_step = step
    return {"srv": srv, "history": history, "events": events, "dispatched": dispatched}


@pytest.fixture(scope="module")
def traced_drop(dataset, tmp_path_factory):
    """A batched-engine run under a trace in which one participant drops every round."""
    srv = _degraded_server(dataset, "batched", "drop")
    history, events = _traced_run(srv, tmp_path_factory.mktemp("trace_drop"))
    return {"srv": srv, "history": history, "events": events}


def test_one_round_span_per_round_with_its_step_number(traced):
    rounds = [e for e in traced["events"] if e[0] == "fl.round"]
    assert [e[3]["step_num"] for e in rounds] == list(range(ROUNDS))
    assert [r.round for r in traced["history"].records] == list(range(ROUNDS))


@pytest.mark.parametrize("child", sorted(PARENT))
def test_each_span_nests_in_its_parent_once_per_round(traced, traced_drop, child):
    events = (traced_drop if child in DEGRADED_ONLY else traced)["events"]
    mine = [e for e in events if e[0] == child]
    per_round = 2 if child == "fl.record" else 1  # opened on both sides of eval
    assert len(mine) == per_round * ROUNDS
    for e in mine:
        assert len(_enclosing(events, e, PARENT[child])) == 1
        assert len(_enclosing(events, e, "fl.round")) == 1


def test_byte_counters_equal_the_device_arrays_built(traced):
    events = traced["events"]
    dispatch = [e[3]["bytes"] for e in events if e[0] == "fl.local_work.dispatch"]
    assert dispatch == traced["dispatched"] and len(dispatch) == ROUNDS
    assert dispatch[0] == 4 * (M + M * 3 * 8 + M + 1)  # slots, indices, weights, stale


def test_slot_counters_match_the_round_records(traced):
    prep = [e[3] for e in traced["events"] if e[0] == "fl.local_work.prep"]
    records = traced["history"].records
    assert [p["distinct"] for p in prep] == [r.n_distinct_clients for r in records]
    assert all(p["slots"] == M for p in prep)


def test_a_traced_run_computes_what_an_untraced_run_computes(dataset, traced):
    plain = _server(dataset)
    history = plain.run()
    assert _canon_json(history) == _canon_json(traced["history"])
    for k, v in plain.params.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(traced["srv"].params[k]))


def test_the_compat_loop_has_the_server_spans_only(dataset, tmp_path):
    _, events = _traced_run(_server(dataset, engine="compat"), tmp_path)
    names = {e[0] for e in events}
    assert names == {"fl.round"} | set(PARENT) - DEGRADED_ONLY - {
        "fl.local_work.prep", "fl.local_work.dispatch", "fl.local_work.wait"}


@pytest.mark.parametrize("engine", ENGINES)
def test_a_round_where_everyone_contributed_hands_observe_the_engines_rows(
    dataset, tmp_path, engine
):
    srv = _server(dataset, engine=engine)
    rounds, observed = _recording(srv)
    _, events = _traced_run(srv, tmp_path)
    assert len(rounds) == len(observed) == ROUNDS
    for (distinct, rows), (_, ids, seen) in zip(rounds, observed):
        assert seen is rows
        np.testing.assert_array_equal(ids, distinct)
    assert not [e for e in events if e[0] == "fl.observe.gather"]


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("cause", ["drop", "late"])
def test_a_round_with_a_drop_or_a_late_client_gathers_the_kept_rows(
    dataset, tmp_path, engine, cause
):
    srv = _degraded_server(dataset, engine, cause)
    rounds, observed = _recording(srv)
    history, events = _traced_run(srv, tmp_path)
    kept = [r.n_distinct_clients - r.n_dropped - r.n_late for r in history.records]
    # the gather opens in exactly the rounds that lost someone and kept someone
    gathered = [k for k, r in zip(kept, history.records) if 0 < k < r.n_distinct_clients]
    assert gathered
    gathers = [e for e in events if e[0] == "fl.observe.gather"]
    assert [int(e[3]["rows"]) for e in gathers] == gathered
    for e in gathers:
        assert len(_enclosing(events, e, "fl.observe")) == 1
    assert [t for t, _, _ in observed] == [t for t, k in enumerate(kept) if k]
    for t, ids, seen in observed:
        distinct, rows = rounds[t]
        keep = np.isin(distinct, ids)
        assert keep.sum() == kept[t] == len(ids)
        np.testing.assert_array_equal(ids, distinct[keep])
        np.testing.assert_array_equal(np.asarray(seen), np.asarray(rows)[keep])
        if cause == "drop":
            assert DROPPED not in ids


@pytest.mark.parametrize("engine", ENGINES)
def test_a_store_backed_sampler_stores_what_a_gathered_copy_stored(dataset, engine):
    """Algorithm 2 without drops: the store holds the engine's rows, and the
    store, the plans, the history and the model equal a run whose sampler
    is fed a gathered copy of those rows, as every round once was."""
    d = int(flatten_params(init_mlp((16, 16, 10), seed=1)).shape[0])

    def run(gathered):
        sampler = Algorithm2Sampler(dataset.population, M, update_dim=d, seed=0)
        if gathered:
            observe = sampler.observe_updates
            sampler.observe_updates = lambda ids, rows: observe(
                ids, rows[np.ones(len(ids), dtype=bool)]
            )
        srv = _server(dataset, engine=engine, sampler=sampler)
        rounds, _ = _recording(srv)
        plans = []

        def on_round(rec):
            distinct, rows = rounds[rec.round]
            store = sampler.gradient_store.asnumpy()
            np.testing.assert_array_equal(store[distinct], np.asarray(rows))
            plans.append((sampler.plan.r.copy(), sampler.plan.cluster_of.copy()))

        srv.run(on_round=on_round)
        sampler.close()
        return srv, plans

    (plain, plain_plans), (copied, copied_plans) = run(False), run(True)
    np.testing.assert_array_equal(
        plain.sampler.gradient_store.asnumpy(), copied.sampler.gradient_store.asnumpy()
    )
    assert len(plain_plans) == len(copied_plans) == ROUNDS
    for (r_a, c_a), (r_b, c_b) in zip(plain_plans, copied_plans):
        np.testing.assert_array_equal(r_a, r_b)
        np.testing.assert_array_equal(c_a, c_b)
    assert _canon_json(plain.history) == _canon_json(copied.history)
    for k, v in plain.params.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(copied.params[k]))


def test_the_test_set_is_staged_once_at_construction(dataset, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        srv = _server(dataset)
        srv.run()
        jax.block_until_ready(srv.params)
    events = _fl_events(str(tmp_path))
    (stage,) = [e for e in events if e[0] == "fl.eval.stage"]
    assert not _enclosing(events, stage, "fl.round")
    assert stage[2] <= min(e[1] for e in events if e[0] == "fl.round")
    assert stage[3]["bytes"] == srv._x_test.nbytes + srv._y_test.nbytes == srv._test_bytes


@pytest.mark.parametrize("engine", ["batched", "compat"])
def test_every_round_evaluates_on_the_same_device_arrays(dataset, tmp_path, engine):
    srv = _server(dataset, engine=engine)
    seen, acc_fn = [], srv.acc_fn

    def recording_acc(params, x, y):
        seen.append((x, y))
        return acc_fn(params, x, y)

    srv.acc_fn = recording_acc
    _, events = _traced_run(srv, tmp_path)
    assert len(seen) == ROUNDS
    x0, y0 = seen[0]
    assert isinstance(x0, jax.Array) and isinstance(y0, jax.Array)
    assert all(x is x0 and y is y0 for x, y in seen)
    assert not [e for e in events if e[0] == "fl.eval.h2d"]
    assert len([e for e in events if e[0] == "fl.eval.run"]) == ROUNDS


def test_staged_accuracy_equals_plain_accuracy_on_host_copies(dataset):
    x_host, y_host = dataset.global_test()
    srv = _server(dataset)
    plain = []
    srv.run(on_round=lambda _: plain.append(float(accuracy(srv.params, x_host, y_host))))
    staged = srv.history.series("test_acc")
    assert len(plain) == ROUNDS
    np.testing.assert_allclose(staged, plain, rtol=0, atol=1.0 / len(y_host))


class _OddRoundsEmptySampler(ClientSampler):
    """Gives its draw zero weight in odd rounds, which makes them empty."""

    def sample(self, round_idx):
        weights = np.zeros(self.population.n_clients)
        if round_idx % 2 == 0:
            weights[:M] = 1.0 / M
        return SampleResult(clients=np.arange(M, dtype=np.int64), agg_weights=weights)


def test_an_empty_round_is_a_round_span_too(dataset, tmp_path):
    srv = _server(dataset, sampler=_OddRoundsEmptySampler(dataset.population, M))
    with jax.profiler.trace(str(tmp_path)):
        history = srv.run(skip_empty=True)
    events = _fl_events(str(tmp_path))
    assert [r.round_status for r in history.records] == ["ok", "empty", "ok"]
    rounds = [e for e in events if e[0] == "fl.round"]
    assert [e[3]["step_num"] for e in rounds] == [0, 1, 2]
    # the empty round ends at the draw: nothing of local work or eval in it
    inside = {e[0] for e in events if _enclosing(events, e, "fl.round") == [rounds[1]]}
    assert inside == {"fl.round", "fl.availability", "fl.draw"}
    with pytest.raises(EmptyRoundError):
        _server(dataset, sampler=_OddRoundsEmptySampler(dataset.population, M)).run()
