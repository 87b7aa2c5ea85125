"""A configuration, a traffic mix and a per-layer metric, each added as a new
file, make a runnable cell: only ``BENCHMARK.json`` gains entries."""
import hashlib
import json

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
