"""The program's own ``fl.*`` spans beside the harness's: a traced run still reads
every per-layer metric of the cell from the harness's spans and the trace."""
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
