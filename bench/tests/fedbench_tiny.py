"""A copy of the benchmark's tree with tiny cells, for tests on the CPU."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parent
for path in (BENCH_DIR, REPO / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

#: Tiny stand-ins of the two configurations: every size shrunk, shapes kept.
TINY = {
    "tiny-shards": ("fig1-mnist-mlp", {"clients_per_class": 2, "n_classes": 4,
                                       "train_per_client": 40, "test_per_client": 10,
                                       "dim": 16}),
    "tiny-dirichlet": ("fig2-cifar-mlp", {"size_profile": [[2, 40], [3, 60], [3, 100]],
                                          "dim": 24}),
}
TRAIN = {"m": 4, "n_local_steps": 3, "batch_size": 8}
#: Cells of the tiny tree: (name, config, traffic, limits borrowed from).
CELLS = (
    ("tiny-shards.alg2-sync", "tiny-shards", "alg2-sync", "fig1-mnist.alg2-sync"),
    ("tiny-shards.md", "tiny-shards", "md", "fig1-mnist.md"),
    ("tiny-dirichlet.alg2-sync", "tiny-dirichlet", "alg2-sync", "fig2-cifar.alg2-sync"),
)
#: The CPU trace's stand-in for a device: XLA's executor threads on the host.
CPU_TRACE = {"device_prefix": "/host:CPU", "op_line": lambda name: name.startswith("tf_XLA")}


def make_tree(root: Path) -> Path:
    """Copy the benchmark to ``root`` and add the tiny cells; returns ``root``."""
    shutil.copytree(BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, (base, data) in TINY.items():
        cfg = json.loads((BENCH_DIR / "configs" / f"{base}.json").read_text())
        cfg["data"].update(data)
        cfg["train"].update(TRAIN)
        (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test", "reduced": [],
                                "file": f"bench/configs/{name}.json"})
    for name, cfg, traffic, limits_of in CELLS:
        spec["workloads"].append({"name": name, "config": cfg, "traffic": traffic,
                                  "chips": 1, "why": "test"})
        shutil.copy(BENCH_DIR / "limits" / f"{limits_of}.json",
                    root / "bench" / "limits" / f"{name}.json")
        for metric in spec["per_layer"]:
            if limits_of in metric["workloads"]:
                metric["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def bench(root: Path):
    import harness

    return harness.Bench(root, root / "bench")


def run(root: Path, cell: str, *, seconds: float = 1.0, trace: bool = False, seed: int = 2**31 + 7):
    """One run of ``cell`` in the tree at ``root``, its trace read from the CPU's
    lines against the peaks of a v5e chip."""
    import time

    import harness

    saved = harness.TRACE_LINES, harness.load_peaks
    harness.TRACE_LINES = CPU_TRACE
    harness.load_peaks = lambda kind: saved[1]("TPU v5 lite")
    try:
        return harness.run_cell(bench(root), cell, seed, seconds, trace, time.perf_counter())
    finally:
        harness.TRACE_LINES, harness.load_peaks = saved
