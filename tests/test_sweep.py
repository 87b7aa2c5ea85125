"""The campaign layer: SweepSpec round-trips and grid expansion, paired
SeedSequence-derived seeds, content-hash cell identity, RunStore resume
semantics (kill + re-invoke ⇒ bit-identical collated CSVs), collation
mean±std against hand-computed references, single-cell ≡ run_spec parity,
process-pool fan-out parity, and History/RoundRecord serialization."""
import json

import numpy as np
import pytest

from repro.fl.experiment import ExperimentSpec, build_experiment
from repro.fl.history import History, RoundRecord
from repro.fl.sweep import (
    SUMMARY_STATS,
    RunStore,
    SweepSpec,
    cell_group_label,
    cell_hash,
    collate,
    run_sweep,
    set_by_path,
    summarize_history,
    write_collated,
)

DATA = {
    "name": "by_class_shards",
    "options": {
        "n_classes": 4, "clients_per_class": 3, "dim": 8, "noise": 0.8,
        "train_per_client": 40, "test_per_client": 8,
    },
}
BASE = {
    "data": DATA,
    "sampler": {"name": "md", "m": 4},
    "train": {"n_rounds": 3, "n_local_steps": 4, "batch_size": 16, "hidden": [16], "lr": 0.08},
}


def _sweep(axes: "dict | None" = None, n_seeds: int = 1, root_seed: int = 7) -> SweepSpec:
    return SweepSpec.from_dict(
        {"base": BASE, "axes": axes or {}, "n_seeds": n_seeds, "root_seed": root_seed}
    )


# --------------------------------------------------------------------------
# spec round-trips + validation
# --------------------------------------------------------------------------
def test_sweep_spec_round_trip_identity():
    sweep = _sweep({"sampler.name": ["md", "algorithm1"]}, n_seeds=3, root_seed=11)
    assert SweepSpec.from_dict(sweep.to_dict()) == sweep
    assert SweepSpec.from_json(sweep.to_json()) == sweep
    json.loads(sweep.to_json())  # valid JSON


def test_sweep_spec_precise_errors():
    with pytest.raises(ValueError, match=r"SweepSpec\.from_dict: unknown key\(s\) \['grid'\]"):
        SweepSpec.from_dict({"base": BASE, "grid": {}})
    with pytest.raises(ValueError, match=r"missing required key\(s\) \['base'\]"):
        SweepSpec.from_dict({"axes": {}})
    with pytest.raises(ValueError, match="non-empty list"):
        _sweep({"sampler.name": []})
    with pytest.raises(ValueError, match="n_seeds"):
        _sweep(n_seeds=0)


def test_set_by_path_rejects_descent_into_scalar():
    d = {"sampler": {"m": 4}}
    with pytest.raises(ValueError, match="cannot descend"):
        set_by_path(d, "sampler.m.deep", 1)


# --------------------------------------------------------------------------
# grid expansion: determinism, ordering, hashes, seeds
# --------------------------------------------------------------------------
def test_grid_expansion_deterministic_and_ordered():
    sweep = _sweep(
        {"train.n_local_steps": [2, 4], "sampler.name": ["md", "algorithm1"]}, n_seeds=2
    )
    a, b = sweep.cells(), sweep.cells()
    assert [c.cell_id for c in a] == [c.cell_id for c in b]  # re-expansion identical
    assert len(a) == 2 * 2 * 2
    # declaration order: first axis outermost, seed axis innermost
    assert [(c.grid_index, c.seed_index) for c in a] == [
        (0, 0), (0, 1), (1, 0), (1, 1), (2, 0), (2, 1), (3, 0), (3, 1)
    ]
    assert a[0].overrides == {"train.n_local_steps": 2, "sampler.name": "md"}
    assert a[2].overrides == {"train.n_local_steps": 2, "sampler.name": "algorithm1"}
    assert a[4].overrides == {"train.n_local_steps": 4, "sampler.name": "md"}


def test_cell_hash_is_content_identity():
    sweep = _sweep({"sampler.name": ["md", "algorithm1"]})
    cells = sweep.cells()
    # the hash is a pure function of the resolved spec
    for c in cells:
        assert c.cell_id == cell_hash(c.spec) == cell_hash(c.spec.to_dict())
    assert len({c.cell_id for c in cells}) == len(cells)
    # a one-knob change changes it; key order does not
    d = cells[0].spec.to_dict()
    reordered = json.loads(json.dumps(d, sort_keys=True))
    assert cell_hash(reordered) == cells[0].cell_id
    d["train"]["lr"] = 0.09
    assert cell_hash(d) != cells[0].cell_id


def test_duplicate_resolved_cells_error():
    with pytest.raises(ValueError, match="identical spec"):
        _sweep({"sampler.name": ["md", "md"]}).cells()


def test_seeds_paired_across_grid_and_distinct_across_replicates():
    sweep = _sweep({"sampler": [{"name": "md", "m": 4}, {"name": "algorithm1", "m": 4}]},
                   n_seeds=2)
    cells = sweep.cells()
    by = {(c.grid_index, c.seed_index): c.spec for c in cells}
    triple = lambda s: (s.data.options["seed"], s.sampler.seed, s.train.seed)
    # same replicate ⇒ same (data, sampler, train) seeds across schemes
    assert triple(by[(0, 0)]) == triple(by[(1, 0)])
    assert triple(by[(0, 1)]) == triple(by[(1, 1)])
    # different replicates ⇒ independent streams (no seed monoculture) —
    # even though the "sampler" axis replaced the whole section dict
    assert triple(by[(0, 0)]) != triple(by[(0, 1)])
    # derivation is a pure function of (root_seed, n_seeds)
    assert sweep.replicate_seeds() == _sweep(n_seeds=2).replicate_seeds()
    assert _sweep(root_seed=8, n_seeds=2).replicate_seeds() != sweep.replicate_seeds()
    # every cell spec carries its replicate's derived value at all three paths
    seeds = sweep.replicate_seeds()
    for c in cells:
        expect = seeds[c.seed_index]
        assert c.spec.data.options["seed"] == expect["data.options.seed"]
        assert c.spec.sampler.seed == expect["sampler.seed"]
        assert c.spec.train.seed == expect["train.seed"]


def test_explicit_seed_axis_wins_over_derivation():
    sweep = _sweep({"data.options.seed": [123, 456]}, n_seeds=1)
    cells = sweep.cells()
    assert [c.spec.data.options["seed"] for c in cells] == [123, 456]
    # the other seed paths still derive
    assert cells[0].spec.train.seed == sweep.replicate_seeds()[0]["train.seed"]


def test_axis_value_dicts_are_not_mutated_by_expansion():
    sampler_axis = [{"name": "md", "m": 4}, {"name": "algorithm1", "m": 4}]
    _sweep({"sampler": sampler_axis}, n_seeds=2).cells()
    assert sampler_axis == [{"name": "md", "m": 4}, {"name": "algorithm1", "m": 4}]


def test_cell_group_label():
    assert cell_group_label({"data.options.alpha": 0.01, "sampler": {"name": "md", "m": 4}}) == (
        "alpha=0.01/sampler=md"
    )


# --------------------------------------------------------------------------
# single-cell sweep ≡ run_spec (bit-identical summary)
# --------------------------------------------------------------------------
def _run_spec(spec: ExperimentSpec) -> dict:
    """benchmarks.common.run_spec's exact code path, repro-side."""
    with build_experiment(spec) as srv:
        hist = srv.run()
    return summarize_history(hist, spec.train.n_rounds)


def test_single_cell_sweep_matches_run_spec(tmp_path):
    sweep = _sweep()
    (cell,) = sweep.cells()
    store = run_sweep(sweep, tmp_path / "store")
    stored = store.read_summary(cell.cell_id)
    direct = _run_spec(cell.spec)
    assert stored == direct  # bit-identical floats, same keys
    # and the persisted per-round records rebuild the identical summary
    hist = store.read_history(cell.cell_id)
    assert summarize_history(hist, cell.spec.train.n_rounds) == direct


# --------------------------------------------------------------------------
# resume: kill after k cells + re-invoke ⇒ bit-identical collated CSVs
# --------------------------------------------------------------------------
def _csv_bytes(store: RunStore) -> tuple[bytes, bytes]:
    cells_csv, summary_csv = write_collated(store)
    return cells_csv.read_bytes(), summary_csv.read_bytes()


def test_interrupted_sweep_resumes_bit_identical(tmp_path):
    sweep = _sweep({"sampler.name": ["md", "algorithm1"]}, n_seeds=2)
    ref = run_sweep(sweep, tmp_path / "uninterrupted")
    ref_bytes = _csv_bytes(ref)

    class Kill(Exception):
        pass

    ran = []

    def killer(cell, status, summary, dt):
        ran.append(cell.cell_id)
        if len(ran) == 2:
            raise Kill()

    with pytest.raises(Kill):
        run_sweep(sweep, tmp_path / "resumed", on_cell=killer)
    store = RunStore(tmp_path / "resumed")
    assert len(store.completed(sweep.cells())) == 2
    # simulate a kill mid-write of the 3rd cell: a partial, torn JSONL line
    # without a summary marker — the rerun must truncate it, not append
    third = sweep.cells()[2]
    assert not store.is_complete(third.cell_id)
    store.records_path(third.cell_id).write_text('{"round": 0, "train_l')
    with pytest.raises(ValueError, match="cells incomplete"):
        collate(store)  # collation refuses a partial campaign

    statuses = []
    run_sweep(sweep, tmp_path / "resumed",
              on_cell=lambda c, s, su, dt: statuses.append(s))
    assert sorted(statuses) == ["ran", "ran", "skipped", "skipped"]
    assert _csv_bytes(store) == ref_bytes


def test_store_rejects_foreign_sweep(tmp_path):
    run_sweep(_sweep(), tmp_path / "store")
    with pytest.raises(ValueError, match="different sweep"):
        run_sweep(_sweep(root_seed=8), tmp_path / "store")


def test_tuple_axis_values_survive_manifest_resume(tmp_path):
    """Python-API tuples JSON-normalize to lists; the resume comparison
    must not read that as a foreign sweep."""
    sweep = _sweep({"train.hidden": [(16,), (8, 8)]})
    store = RunStore(tmp_path / "store")
    store.write_manifest(sweep)
    store.write_manifest(sweep)  # re-invoke: must not raise
    assert [c.cell_id for c in store.read_manifest().cells()] == [
        c.cell_id for c in sweep.cells()
    ]


def test_pinned_base_seed_warns_when_derivation_overwrites():
    pinned = {**BASE, "train": {**BASE["train"], "seed": 5}}
    sweep = SweepSpec.from_dict({"base": pinned, "n_seeds": 1})
    with pytest.warns(UserWarning, match=r"pinned at \['train.seed'\] are overwritten"):
        cells = sweep.cells()
    assert cells[0].spec.train.seed == sweep.replicate_seeds()[0]["train.seed"]
    # pinning via a single-value seed axis is the sanctioned (silent) way
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        axis_cells = SweepSpec.from_dict(
            {"base": BASE, "axes": {"train.seed": [5]}, "n_seeds": 1}
        ).cells()
    assert axis_cells[0].spec.train.seed == 5


def test_store_manifest_preserves_axes_order(tmp_path):
    sweep = _sweep({"train.n_local_steps": [2, 4], "sampler.name": ["md", "algorithm1"]})
    store = RunStore(tmp_path / "store")
    store.write_manifest(sweep)
    rt = store.read_manifest()
    assert list(rt.axes) == ["train.n_local_steps", "sampler.name"]
    assert [c.cell_id for c in rt.cells()] == [c.cell_id for c in sweep.cells()]


# --------------------------------------------------------------------------
# collation: mean±std pinned against hand-computed references
# --------------------------------------------------------------------------
def test_collation_mean_std_hand_computed(tmp_path):
    """Fabricated summaries ⇒ exactly predictable aggregate rows."""
    sweep = _sweep({"sampler.name": ["md", "algorithm1"]}, n_seeds=2)
    store = RunStore(tmp_path / "store")
    store.write_manifest(sweep)
    planted = {(0, 0): 1.0, (0, 1): 2.0, (1, 0): 5.0, (1, 1): 5.0}
    for c in sweep.cells():
        v = planted[(c.grid_index, c.seed_index)]
        store.finalize_cell(c.cell_id, {stat: v for stat in SUMMARY_STATS})
    cell_rows, agg_rows = collate(store)
    assert len(cell_rows) == 4 and len(agg_rows) == 2
    md, a1 = agg_rows
    assert (md["sampler.name"], md["n_seeds"]) == ("md", 2)
    # mean(1, 2) = 1.5, population std = 0.5; mean(5, 5) = 5, std = 0
    assert md["final_loss_mean"] == 1.5 and md["final_loss_std"] == 0.5
    assert a1["final_loss_mean"] == 5.0 and a1["final_loss_std"] == 0.0
    # per-cell rows carry the axis column and the raw stat
    assert [r["final_loss"] for r in cell_rows] == [1.0, 2.0, 5.0, 5.0]
    assert all(r["sampler.name"] in ("md", "algorithm1") for r in cell_rows)


def test_collation_matches_numpy_over_real_runs(tmp_path):
    sweep = _sweep(n_seeds=2)
    store = run_sweep(sweep, tmp_path / "store")
    cell_rows, agg_rows = collate(store)
    losses = np.array([r["final_loss"] for r in cell_rows], dtype=np.float64)
    assert agg_rows[0]["final_loss_mean"] == float(losses.mean())
    assert agg_rows[0]["final_loss_std"] == float(losses.std())


# --------------------------------------------------------------------------
# process-pool fan-out ≡ serial, byte for byte
# --------------------------------------------------------------------------
def test_parallel_workers_match_serial(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # the pool runs only on the CPU
    sweep = SweepSpec.from_dict(
        {
            "base": {**BASE, "train": {**BASE["train"], "n_rounds": 2, "n_local_steps": 2}},
            "axes": {"sampler.name": ["md", "algorithm1"]},
            "root_seed": 7,
        }
    )
    serial = run_sweep(sweep, tmp_path / "serial", workers=1)
    pooled = run_sweep(sweep, tmp_path / "pooled", workers=2)
    assert _csv_bytes(pooled) == _csv_bytes(serial)


@pytest.mark.parametrize("platforms", [None, "tpu", "cpu,tpu"])
def test_worker_pool_refused_off_the_cpu(tmp_path, monkeypatch, platforms):
    """Each pool worker would open the accelerator, which serves one process."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        run_sweep(_sweep({"sampler.name": ["md", "algorithm1"]}), tmp_path / "s", workers=2)
    assert not (tmp_path / "s").exists()


# --------------------------------------------------------------------------
# History / RoundRecord serialization round-trips (the RunStore contract)
# --------------------------------------------------------------------------
def test_round_record_round_trip():
    rec = RoundRecord(
        round=3, train_loss=0.25, test_acc=0.75, n_distinct_clients=4,
        n_distinct_classes=3, agg_weights=np.array([0.1, 0.0, 0.9]),
        plan_version=2, plan_lag_rounds=1,
    )
    rt = RoundRecord.from_dict(json.loads(json.dumps(rec.to_dict())))
    np.testing.assert_array_equal(rt.agg_weights, rec.agg_weights)  # f64-exact
    assert rt.agg_weights.dtype == np.float64
    rec_no_w = RoundRecord(round=0, train_loss=1.0, test_acc=0.1,
                           n_distinct_clients=1, n_distinct_classes=1)
    assert RoundRecord.from_dict(rec_no_w.to_dict()) == rec_no_w
    with pytest.raises(ValueError, match=r"RoundRecord\.from_dict: unknown key"):
        RoundRecord.from_dict({"round": 0, "loss": 1.0})


def test_history_json_round_trip():
    hist = History()
    for t in range(3):
        hist.append(RoundRecord(round=t, train_loss=1.0 / (t + 1), test_acc=float(t),
                                n_distinct_clients=2, n_distinct_classes=2,
                                agg_weights=np.array([0.5, 0.5]) * (t + 1)))
    rt = History.from_json(hist.to_json())
    assert len(rt.records) == 3
    np.testing.assert_array_equal(rt.series("train_loss"), hist.series("train_loss"))
    for a, b in zip(rt.records, hist.records):
        np.testing.assert_array_equal(a.agg_weights, b.agg_weights)
    # the documented opt-out drops the weights but stays loadable
    slim = History.from_json(hist.to_json(include_agg_weights=False))
    assert all(r.agg_weights is None for r in slim.records)
    with pytest.raises(ValueError, match="expects a JSON list"):
        History.from_json('{"records": []}')
