"""The declarative experiment layer: dict↔spec round-trips, registry
semantics, spec-built ≡ hand-built parity, lifecycle safety (no leaked
planner workers), rebuild cadence, and the vectorized plan draw."""
import json
import threading

import numpy as np
import pytest

from repro.core import (
    SAMPLERS,
    Algorithm2Sampler,
    ClientPopulation,
    MDSampler,
    register_sampler,
)
from repro.core.samplers.algorithm1 import Algorithm1Sampler
from repro.core.types import SamplingPlan
from repro.fl import ENGINES, FederatedServer, FLConfig, by_class_shards, register_engine
from repro.fl.aggregation import flatten_params
from repro.fl.client import ReferenceRoundEngine, reference_engine
from repro.fl.experiment import (
    DATASETS,
    DataSpec,
    EngineSpec,
    ExperimentSpec,
    PlannerSpec,
    SamplerSpec,
    TrainSpec,
    build_dataset,
    build_experiment,
    build_sampler,
)
from repro.fl.planner import PlanService
from repro.models.simple import init_mlp
from repro.optim import sgd

DATA = {
    "name": "by_class_shards",
    "options": {
        "n_classes": 4, "clients_per_class": 3, "dim": 8, "noise": 0.8,
        "train_per_client": 40, "test_per_client": 8, "seed": 0,
    },
}
TRAIN = {"n_rounds": 3, "n_local_steps": 4, "batch_size": 16, "hidden": [16], "lr": 0.08, "seed": 0}


def _spec(sampler: dict, planner: "dict | None" = None, **train) -> dict:
    d = {"data": DATA, "sampler": sampler, "train": {**TRAIN, **train}}
    if planner is not None:
        d["planner"] = planner
    return d


@pytest.fixture(scope="module")
def dataset():
    return build_dataset(DataSpec.from_dict(DATA))


# --------------------------------------------------------------------------
# dict / json round-trips
# --------------------------------------------------------------------------
def test_spec_dict_round_trip_identity():
    spec = ExperimentSpec.from_dict(
        _spec({"name": "algorithm2", "m": 4, "options": {"measure": "l2"}},
              planner={"mode": "async", "rebuild_every": 2})
    )
    rt = ExperimentSpec.from_dict(spec.to_dict())
    assert rt == spec
    # and through actual JSON text
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    json.loads(spec.to_json())  # valid JSON


def test_sub_specs_round_trip():
    for cls, d in (
        (DataSpec, DATA),
        (SamplerSpec, {"name": "md", "m": 7, "seed": 3}),
        (PlannerSpec, {"mode": "async", "rebuild_every": 5}),
        (EngineSpec, {"name": "batched", "mesh_spec": "2x2"}),
        (TrainSpec, {"n_rounds": 2, "hidden": [8, 8], "n_classes": 4}),
    ):
        spec = cls.from_dict(d)
        assert cls.from_dict(spec.to_dict()) == spec


def test_engine_spec_mesh_tuple_round_trip():
    spec = EngineSpec.from_dict({"mesh_spec": [2, 2]})
    assert spec.mesh_spec == (2, 2)  # JSON list normalizes to the tuple form
    assert spec.to_dict()["mesh_spec"] == [2, 2]
    assert EngineSpec.from_dict(spec.to_dict()) == spec


@pytest.mark.parametrize(
    "cls,d",
    [
        (DataSpec, {"name": "by_class_shards", "optons": {}}),
        (SamplerSpec, {"name": "md", "m": 4, "planner": "sync"}),
        (PlannerSpec, {"mode": "sync", "every": 2}),
        (EngineSpec, {"engine": "batched"}),
        (EngineSpec, {"max_staged_bytes": 123}),
        (TrainSpec, {"rounds": 5}),
        (ExperimentSpec, {"data": DATA, "sampler": {"name": "md", "m": 4}, "sweep": []}),
    ],
)
def test_from_dict_unknown_key_is_precise(cls, d):
    with pytest.raises(ValueError, match=rf"{cls.__name__}\.from_dict: unknown key"):
        cls.from_dict(d)


def test_from_dict_missing_required_key_is_precise():
    with pytest.raises(ValueError, match=r"SamplerSpec\.from_dict: missing required key\(s\) \['m'\]"):
        SamplerSpec.from_dict({"name": "md"})
    with pytest.raises(ValueError, match=r"ExperimentSpec\.from_dict: missing required key"):
        ExperimentSpec.from_dict({})


def test_degenerate_plan_row_fails_fast():
    """A NaN-poisoned or zero-mass plan row must raise, not silently draw
    client 0 (the old per-urn rng.choice validated p every call)."""
    pop = ClientPopulation(np.full(3, 10))
    s = MDSampler(pop, 2, seed=0)
    s._plan = SamplingPlan(r=np.array([[0.5, 0.25, 0.25], [0.0, 0.0, 0.0]]))
    with pytest.raises(ValueError, match="plan row 1 is not a probability"):
        s.sample(0)
    s._plan = SamplingPlan(r=np.array([[np.nan, 0.5, 0.5], [1.0, 0.0, 0.0]]))
    with pytest.raises(ValueError, match="plan row 0 is not a probability"):
        s.sample(0)


def test_planner_spec_validates_eagerly():
    with pytest.raises(ValueError, match="unknown planner mode"):
        PlannerSpec(mode="turbo")
    with pytest.raises(ValueError, match="rebuild_every"):
        PlannerSpec(rebuild_every=0)
    with pytest.raises(ValueError, match="sketch_dim"):
        PlannerSpec(sketch_dim=16)  # a dimension with no sketch is a typo
    with pytest.raises(ValueError, match="sketch_dim"):
        PlannerSpec(sketch="srp", sketch_dim=0)


def test_planner_spec_sketch_round_trip():
    spec = PlannerSpec(sketch="srp", sketch_dim=64)
    d = spec.to_dict()
    assert d["sketch"] == "srp" and d["sketch_dim"] == 64
    assert PlannerSpec.from_dict(d) == spec
    assert not spec.is_default  # a sketched planner is never the no-op one
    assert PlannerSpec(sketch="identity").is_default is False


def test_sketch_threads_from_planner_spec_to_store():
    pop = ClientPopulation(np.full(6, 10))
    s = build_sampler(
        {"name": "algorithm2", "m": 2},
        pop,
        planner=PlannerSpec(sketch="srp", sketch_dim=8),
        update_dim=32,
    )
    try:
        st = s.gradient_store
        assert st.sketch.name == "srp"
        assert (st.update_dim, st.dim) == (32, 8)
        assert st.sketch.seed == 0  # rides SamplerSpec.seed (default 0)
    finally:
        s.close()
    seeded = build_sampler(
        {"name": "algorithm2", "m": 2, "options": {"seed": 5}},
        pop,
        planner=PlannerSpec(sketch="srp", sketch_dim=8),
        update_dim=32,
    )
    try:
        assert seeded.gradient_store.sketch.seed == 5
    finally:
        seeded.close()


# --------------------------------------------------------------------------
# registries
# --------------------------------------------------------------------------
def test_unknown_registry_names_list_known():
    pop = ClientPopulation(np.full(4, 10))
    with pytest.raises(ValueError, match=r"unknown sampler 'nope'.*algorithm2"):
        build_sampler({"name": "nope", "m": 2}, pop)
    with pytest.raises(ValueError, match=r"unknown dataset.*by_class_shards"):
        build_dataset({"name": "imaginary"})
    with pytest.raises(ValueError, match=r"unknown engine.*batched"):
        ENGINES.get("turbo")


def test_unknown_registry_names_suggest_close_match():
    """Near-miss names get a did-you-mean suffix across every registry."""
    from repro.core.clustering import CLUSTERERS
    from repro.fl.population import POPULATIONS
    from repro.kernels.sketch import SKETCHERS

    with pytest.raises(ValueError, match=r"did you mean 'algorithm2'\?"):
        SAMPLERS.get("algorithm2x")
    with pytest.raises(ValueError, match=r"did you mean 'ward'\?"):
        CLUSTERERS.get("wardd")
    with pytest.raises(ValueError, match=r"did you mean 'srp'\?"):
        SKETCHERS.get("srpp")
    with pytest.raises(ValueError, match=r"did you mean 'poisson'\?"):
        POPULATIONS.get("poissonn")
    # gibberish far from every entry: the listing stays, no suggestion
    with pytest.raises(ValueError, match=r"unknown sampler") as ei:
        SAMPLERS.get("zzqx")
    assert "did you mean" not in str(ei.value)


def test_sampler_options_checked_against_signature():
    pop = ClientPopulation(np.full(4, 10))
    with pytest.raises(ValueError, match=r"'algorithm2' does not accept option\(s\) \['measur'\]"):
        build_sampler({"name": "algorithm2", "m": 2, "options": {"measur": "l2"}}, pop)


def test_update_dim_required_for_similarity_sampler():
    pop = ClientPopulation(np.full(4, 10))
    with pytest.raises(ValueError, match="needs update_dim"):
        build_sampler({"name": "algorithm2", "m": 2}, pop)


def test_non_default_planner_rejected_for_planless_sampler():
    pop = ClientPopulation(np.full(4, 10))
    with pytest.raises(ValueError, match="has no plan service"):
        build_sampler({"name": "md", "m": 2}, pop, planner=PlannerSpec(mode="async"))
    # the default planner is a no-op and passes through
    s = build_sampler({"name": "md", "m": 2}, pop, planner=PlannerSpec())
    assert isinstance(s, MDSampler)


def test_register_sampler_override_and_unregister():
    class HalfSampler(MDSampler):
        pass

    register_sampler("half-md", HalfSampler)
    try:
        with pytest.raises(ValueError, match="already registered"):
            register_sampler("half-md", MDSampler)
        register_sampler("half-md", HalfSampler, override=True)
        pop = ClientPopulation(np.full(4, 10))
        s = build_sampler({"name": "half-md", "m": 2}, pop)
        assert isinstance(s, HalfSampler)
    finally:
        SAMPLERS.unregister("half-md")
    assert "half-md" not in SAMPLERS


def test_register_engine_reaches_server(dataset):
    calls = []

    def probe_engine(ds, m, config, mesh):
        calls.append((m, config.engine))
        return reference_engine(ds, m, config, mesh)

    register_engine("probe", probe_engine)
    try:
        spec = ExperimentSpec.from_dict(_spec({"name": "md", "m": 4}, n_rounds=1))
        spec = ExperimentSpec.from_dict({**spec.to_dict(), "engine": {"name": "probe"}})
        with build_experiment(spec, dataset=dataset) as srv:
            hist = srv.run()
        assert calls == [(4, "probe")]
        assert isinstance(srv._engine, ReferenceRoundEngine)
        assert np.isfinite(hist.series("train_loss")).all()
    finally:
        ENGINES.unregister("probe")


# --------------------------------------------------------------------------
# spec-built ≡ hand-built (bit-identical History for fixed seeds)
# --------------------------------------------------------------------------
def _hand_built(dataset, name: str, planner: str) -> FederatedServer:
    pop = dataset.population
    params = init_mlp((8, 16, 4), seed=1)
    d = int(flatten_params(params).shape[0])
    if name == "md":
        sampler = MDSampler(pop, 4, seed=0)
    elif name == "algorithm1":
        sampler = Algorithm1Sampler(pop, 4, seed=0)
    else:
        sampler = Algorithm2Sampler(pop, 4, update_dim=d, seed=0, planner=planner)
    cfg = FLConfig(n_rounds=3, n_local_steps=4, batch_size=16, seed=0)
    return FederatedServer(dataset, sampler, params, sgd(0.08), cfg)


def _run_forced(srv: FederatedServer):
    """Round loop that forces any async rebuild to land between rounds, so
    async runs are deterministic and comparable across servers."""
    for t in range(srv.cfg.n_rounds):
        srv.run_round(t)
        if hasattr(srv.sampler, "flush_plan"):
            srv.sampler.flush_plan()
    return srv.history


@pytest.mark.parametrize(
    "name,planner",
    [("md", "sync"), ("algorithm1", "sync"), ("algorithm2", "sync"), ("algorithm2", "async")],
)
def test_spec_built_matches_hand_built_bit_identical(dataset, name, planner):
    spec = _spec({"name": name, "m": 4}, planner={"mode": planner})
    with build_experiment(spec, dataset=dataset) as a, _hand_built(dataset, name, planner) as b:
        ha, hb = _run_forced(a), _run_forced(b)
        for field in ("train_loss", "test_acc", "n_distinct_clients",
                      "n_distinct_classes", "plan_version", "plan_lag_rounds"):
            np.testing.assert_array_equal(ha.series(field), hb.series(field), err_msg=field)
        np.testing.assert_array_equal(
            np.stack([r.agg_weights for r in ha.records]),
            np.stack([r.agg_weights for r in hb.records]),
        )
        np.testing.assert_array_equal(
            np.asarray(flatten_params(a.params)), np.asarray(flatten_params(b.params))
        )


def test_dict_round_trip_rebuilds_identical_history(dataset):
    """Acceptance: ExperimentSpec.from_dict(spec.to_dict()) rebuilds an
    experiment whose History is bit-identical for fixed seeds."""
    spec = ExperimentSpec.from_dict(_spec({"name": "algorithm2", "m": 4}))
    with build_experiment(spec, dataset=dataset) as a, build_experiment(
        ExperimentSpec.from_dict(spec.to_dict()), dataset=dataset
    ) as b:
        ha, hb = a.run(), b.run()
        np.testing.assert_array_equal(ha.series("train_loss"), hb.series("train_loss"))
        np.testing.assert_array_equal(ha.series("test_acc"), hb.series("test_acc"))


# --------------------------------------------------------------------------
# lifecycle: the context-managed server owns the planner worker
# --------------------------------------------------------------------------
def _planner_threads():
    return [t for t in threading.enumerate() if t.name == "plan-service" and t.is_alive()]


def test_context_manager_reaps_async_planner_worker(dataset):
    assert _planner_threads() == []
    with build_experiment(
        _spec({"name": "algorithm2", "m": 4}, planner={"mode": "async"}), dataset=dataset
    ) as srv:
        srv.run()
        srv.sampler.flush_plan()
        assert len(_planner_threads()) == 1  # worker exists inside the block
    assert _planner_threads() == []  # ...and never survives it
    srv.close()  # idempotent


def test_close_is_idempotent_and_explicit(dataset):
    srv = build_experiment(
        _spec({"name": "algorithm2", "m": 4}, planner={"mode": "async"}, n_rounds=1),
        dataset=dataset,
    )
    srv.run()
    srv.close()
    srv.close()
    assert _planner_threads() == []


# --------------------------------------------------------------------------
# planner rebuild cadence (PlannerSpec.rebuild_every)
# --------------------------------------------------------------------------
def test_plan_service_rebuild_cadence():
    pop = ClientPopulation(np.full(6, 10))
    built = []

    def build(G):
        built.append(G)
        return SamplingPlan(r=np.tile(pop.importances, (2, 1)))

    svc = PlanService(build, mode="sync", rebuild_every=2)
    assert svc.current().version == 0 and len(built) == 1
    svc.observe("a")
    assert svc.poll() is None and len(built) == 1  # skipped observation
    assert svc.telemetry() == (0, 1)  # ...but the lag records it
    svc.observe("b")
    vp = svc.poll()
    assert vp is not None and vp.version == 2 and len(built) == 2
    assert built[-1] == "b"  # the cadence-triggering snapshot is the cumulative one
    svc.observe("c")
    assert svc.poll() is None and svc.telemetry() == (2, 1)
    with pytest.raises(ValueError, match="rebuild_every"):
        PlanService(build, rebuild_every=0)


def test_rebuild_cadence_lands_in_round_telemetry(dataset):
    spec = _spec(
        {"name": "algorithm2", "m": 4},
        planner={"mode": "sync", "rebuild_every": 2},
        n_rounds=4,
    )
    with build_experiment(spec, dataset=dataset) as srv:
        hist = srv.run()
    np.testing.assert_array_equal(hist.series("plan_version"), [0, 0, 2, 2])
    np.testing.assert_array_equal(hist.series("plan_lag_rounds"), [0, 1, 0, 1])


# --------------------------------------------------------------------------
# streaming per-round callback
# --------------------------------------------------------------------------
def test_run_streams_records_through_on_round(dataset):
    seen = []
    with build_experiment(_spec({"name": "md", "m": 4}), dataset=dataset) as srv:
        hist = srv.run(on_round=seen.append)
    assert seen == hist.records


# --------------------------------------------------------------------------
# vectorized plan draw ≡ the per-urn rng.choice loop, bit for bit
# --------------------------------------------------------------------------
def test_vectorized_draw_matches_choice_loop_bitwise():
    pop = ClientPopulation(
        np.concatenate([np.full(10, 100), np.full(20, 500), np.full(10, 1000)])
    )
    s = MDSampler(pop, 12, seed=11)
    drawn = [s.sample(t).clients for t in range(30)]
    rng = np.random.default_rng(11)  # replay the exact uniform stream
    for clients in drawn:
        ref = np.array(
            [rng.choice(pop.n_clients, p=s.plan.r[k]) for k in range(s.plan.m)]
        )
        np.testing.assert_array_equal(clients, ref)


def test_inferred_n_classes_and_update_dim(dataset):
    with build_experiment(_spec({"name": "algorithm2", "m": 4}, n_rounds=1), dataset=dataset) as srv:
        d_model = int(flatten_params(srv.params).shape[0])
        # 8 -> 16 -> 4 MLP: inferred 4 classes, inferred update_dim
        assert srv.params["w1"].shape == (16, 4)
        assert srv.sampler.update_dim == d_model
        srv.run()


def test_load_spec_dict_inline_file_and_errors(tmp_path):
    from repro.fl.experiment import load_spec_dict

    assert load_spec_dict('{"a": 1}') == {"a": 1}
    p = tmp_path / "spec.json"
    p.write_text('{"b": 2}')
    assert load_spec_dict(str(p)) == {"b": 2}
    with pytest.raises(ValueError, match="neither an existing file nor valid JSON"):
        load_spec_dict("definitely-not-json")
    with pytest.raises(ValueError, match="must be an object"):
        load_spec_dict("[1, 2]")


def test_lm_config_sampler_spec_m_guard():
    from repro.launch.fl_train import FLLMConfig

    # a dict may omit m/seed — they inherit the config's
    fl = FLLMConfig(m=4, seed=7, sampler={"name": "md"})
    spec = fl.sampler_spec()
    assert (spec.m, spec.seed) == (4, 7)
    # a contradicting m fails fast with a precise error
    with pytest.raises(ValueError, match="contradicts FLLMConfig.m"):
        FLLMConfig(m=4, sampler={"name": "md", "m": 3}).sampler_spec()


def test_lm_config_resolves_through_spec_path():
    from repro.launch.fl_train import FLLMConfig, make_lm_sampler

    pop = ClientPopulation(np.full(8, 100))
    fl = FLLMConfig(
        m=4, sampler={"name": "algorithm2", "m": 4, "options": {"measure": "l2"}},
        planner={"mode": "async", "rebuild_every": 3},
    )
    s = make_lm_sampler(fl, pop, update_dim=16)
    try:
        assert isinstance(s, Algorithm2Sampler)
        assert s.measure == "l2"
        assert s.plan_service.mode == "async"
        assert s.plan_service.rebuild_every == 3
    finally:
        s.close()
    with pytest.raises(ValueError, match="has no plan service"):
        make_lm_sampler(FLLMConfig(m=4, sampler="md", planner="async"), pop, 0)
