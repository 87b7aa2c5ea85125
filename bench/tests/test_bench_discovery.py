"""A configuration, a traffic mix, a per-layer metric and a model kind, each
added as a new file, make a runnable cell: only ``BENCHMARK.json`` gains entries."""
import hashlib
import json
import shutil

import fedbench_tiny as ft


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_files_make_a_new_cell(tmp_path):
    root = ft.make_tree(tmp_path)
    before = _digests(root)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "tiny-shards.json").read_text())
    cfg["data"]["dim"] = 12
    (bench / "configs" / "wide-shards.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "mixes" / "alg2-sync.json").read_text())
    mix["why"] = "Algorithm 2 again, under a name of its own"
    (bench / "mixes" / "alg2-again.json").write_text(json.dumps(mix))
    (bench / "limits" / "wide-shards.alg2-again.json").write_text(
        (bench / "limits" / "fig1-mnist.alg2-sync.json").read_text())
    (bench / "layer_metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.rounds)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "wide-shards", "source": "test", "reduced": [],
                            "file": "bench/configs/wide-shards.json"})
    spec["workloads"].append({"name": "wide-shards.alg2-again", "config": "wide-shards",
                              "traffic": "alg2-again", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "rounds_traced", "unit": "rounds", "better": "higher",
                              "source": "host_clock", "layer": "device", "moves": "round_ms",
                              "workloads": ["wide-shards.alg2-again"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(_digests(root)[p] == d for p, d in before.items())

    timed = ft.run(root, "wide-shards.alg2-again", seconds=0.5)
    assert timed["correct"], timed["checks"]
    assert set(timed["metrics"]) == {"round_ms", "round_p95_ms", "setup_s"}
    traced = ft.run(root, "wide-shards.alg2-again", seconds=0.5, trace=True)
    assert traced["correct"], traced["checks"]
    assert traced["metrics"]["rounds_traced"]["value"] == traced["attempted"] >= 1
    assert list(traced)[-1] == "checks"


def test_a_model_kind_added_as_files_makes_a_new_cell(tmp_path, capfd):
    """A second model kind, with weights in a nested pytree and its own loss and
    accuracy in the program, runs a correct cell from new files alone."""
    root = ft.make_tree(tmp_path)
    before = _digests(root)
    bench = root / "bench"
    shutil.copy(ft.BENCH_DIR / "tests" / "toy_kind.py", bench / "models" / "toy.py")
    cfg = json.loads((bench / "configs" / "tiny-shards.json").read_text())
    del cfg["train"]["hidden"]
    cfg["model"] = {"kind": "toy", "width": 6}
    (bench / "configs" / "toy-shards.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "mixes" / "md.json").read_text())
    mix["why"] = "MD sampling for the toy model"
    (bench / "mixes" / "md-toy.json").write_text(json.dumps(mix))
    (bench / "limits" / "toy-shards.md-toy.json").write_text(
        (bench / "limits" / "fig1-mnist.md.json").read_text())
    (bench / "layer_metrics" / "train_mflops_per_round.py").write_text(
        "def read(ctx):\n"
        "    per = ctx.shapes['train_flops_per_client']\n"
        "    return sum(per * r.n_distinct_clients for r in ctx.records) / ctx.rounds / 1e6\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = "toy-shards.md-toy"
    spec["configs"].append({"name": "toy-shards", "source": "test", "reduced": [],
                            "file": "bench/configs/toy-shards.json"})
    spec["workloads"].append({"name": cell, "config": "toy-shards", "traffic": "md-toy",
                              "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if "fig1-mnist.md" in metric["workloads"]:
            metric["workloads"].append(cell)
    spec["per_layer"].append({"name": "train_mflops_per_round", "unit": "MFLOP",
                              "better": "higher", "source": "host_clock", "layer": "device",
                              "moves": "round_ms", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(_digests(root)[p] == d for p, d in before.items())

    timed = ft.run(root, cell, seconds=0.5)
    assert timed["correct"], timed["checks"]
    assert set(timed["metrics"]) == {"round_ms", "round_p95_ms", "setup_s"}
    assert "check: 3 rounds" in capfd.readouterr().err  # the kind's own round count
    traced = ft.run(root, cell, seconds=0.5, trace=True)
    assert traced["correct"], traced["checks"]
    listed = {m["name"] for m in ft.bench(root).metrics(cell, "per_layer")}
    # a CPU trace has no device module line, so the round step's device time reads nothing
    assert set(traced["metrics"]) == listed - {"round_step_device_ms"}
    assert all(m["value"] > 0 for m in traced["metrics"].values())
    n_params = 16 * 6 + 6 + 6 * 4 + 4  # tiny-shards: 16 features, 4 classes
    per_client = 6 * n_params * ft.TRAIN["n_local_steps"] * ft.TRAIN["batch_size"]
    got = traced["metrics"]["train_mflops_per_round"]["value"] * 1e6
    assert per_client <= got <= ft.TRAIN["m"] * per_client
