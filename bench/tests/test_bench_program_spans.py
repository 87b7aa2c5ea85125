"""The program's own ``fl.*`` spans beside the harness's: a traced run still reads
every per-layer metric of the cell from the harness's spans and the trace, and
a reader added as a file reads the program's spans and their counters."""
import fedbench_tiny as ft


def test_a_traced_run_reads_the_cells_metrics_beside_the_program_spans(tmp_path):
    root = ft.make_tree(tmp_path)
    res = ft.run(root, "tiny-shards.md", seconds=0.5, trace=True)
    assert res["correct"], res["checks"]
    listed = {m["name"] for m in ft.bench(root).metrics("tiny-shards.md", "per_layer")}
    # a CPU trace has no device module line, so the round step's device time reads nothing
    assert set(res["metrics"]) == listed - {"round_step_device_ms"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert all(label in {"round", "draw", "local_work", "observe", "eval", "none"}
               for label, _ in res["breakdown"]["idle_gaps"])


READERS = {
    "padded_slot_share": "t = ctx.arg_totals('fl.local_work.prep')\n"
                         "    return 100.0 * (t['slots'] - t['distinct']) / t['slots'] if t else None",
    "prep_slots": "return ctx.arg_totals('fl.local_work.prep').get('slots')",
    "local_work_program_ms": "return ctx.program_ms_per_round('fl.local_work')",
    "round_program_ms": "return ctx.program_ms_per_round('fl.round')",
}


def test_a_reader_added_as_a_file_reads_program_spans_and_counters(tmp_path):
    import json

    root = ft.make_tree(tmp_path)
    cell = "tiny-shards.md"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for name, body in READERS.items():
        (root / "bench" / "layer_metrics" / f"{name}.py").write_text(
            f"def read(ctx):\n    {body}\n")
        spec["per_layer"].append({"name": name, "unit": "x", "better": "lower",
                                  "source": "program_span", "layer": "round engine",
                                  "moves": "round_ms", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    res = ft.run(root, cell, seconds=0.5, trace=True)
    assert res["correct"], res["checks"]
    got = {name: res["metrics"][name]["value"] for name in READERS}
    # one fl.local_work.prep per round of the window, each counting m slots
    assert got["prep_slots"] == ft.TRAIN["m"] * res["attempted"]
    assert 0 <= got["padded_slot_share"] < 100
    assert 0 < got["local_work_program_ms"] < got["round_program_ms"]
    # the program's spans label no idle gap: the labels stay the harness's
    assert all(label in {"round", "draw", "local_work", "observe", "eval", "none"}
               for label, _ in res["breakdown"]["idle_gaps"])
