"""Benchmark harness — one module per paper table/figure.

``--spec '<json>'`` (inline or a file path) instead runs ONE declarative
experiment through ``repro.fl.experiment`` and streams its per-round
records — the scenario door for comparison studies and the tier-1 smoke
for the spec layer.

``--sweep '<json>'`` runs a whole campaign (``repro.fl.sweep.SweepSpec``:
grid × seeds) into a resumable RunStore (``--store DIR``, ephemeral when
omitted; ``--workers k`` fans independent cells over a process pool) and
collates it into figure-ready CSVs. ``--list`` prints every registered
sampler / engine / dataset / population / clusterer / benchmark module —
the discoverability door for the spec and sweep layers.

Prints ``name,us_per_call,derived`` CSV rows:
  fig1_controlled      — Figure 1 (controlled MNIST-style setting)
  fig2_dirichlet       — Figure 2 (Dirichlet-α heterogeneity sweep)
  table_variance       — Section 3.2 / Appendix B statistics (theory vs MC)
  ablations            — Appendix D.2/D.4/D.5
  beyond_paper         — extensions: stale-aware Algorithm 2, device similarity, churn
  scheme_race          — every registered selection scheme raced on one sweep
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (
    ablations,
    beyond_paper,
    fig1_controlled,
    fig2_dirichlet,
    scheme_race,
    table_variance,
)
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("table_variance", table_variance),
    ("fig1_controlled", fig1_controlled),
    ("fig2_dirichlet", fig2_dirichlet),
    ("scheme_race", scheme_race),
    ("ablations", ablations),
    ("beyond_paper", beyond_paper),
]


def run_one_spec(spec_arg: str) -> None:
    """Run a single experiment spec (inline JSON or a path to a JSON file)."""
    from benchmarks.common import emit, run_spec
    from repro.fl.experiment import ExperimentSpec

    spec = ExperimentSpec.from_arg(spec_arg)
    label = f"spec/{spec.data.name}/{spec.sampler.name}"
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    res = run_spec(  # per-round records stream through the server's hook
        spec,
        on_round=lambda rec: emit(
            f"{label}/round={rec.round}", 0.0,
            f"loss={rec.train_loss:.4f};plan_v={rec.plan_version};"
            f"lag={rec.plan_lag_rounds}",
        ),
    )
    us = (time.perf_counter() - t0) * 1e6 / spec.train.n_rounds
    emit(label, us, f"loss={res['final_loss']:.4f};acc={res['final_acc']:.3f}")


def run_one_sweep(sweep_arg: str, store_dir: "str | None", workers: int) -> None:
    """Run a whole campaign through the resumable sweep runner + collate."""
    import contextlib
    import tempfile

    from benchmarks.common import emit
    from repro.fl.sweep import SweepSpec, cell_group_label, run_sweep, write_collated

    sweep = SweepSpec.from_arg(sweep_arg)
    print("name,us_per_call,derived")

    def on_cell(cell, status, summary, dt):
        label = cell_group_label(cell.overrides) or "base"
        rounds = max(cell.spec.train.n_rounds, 1)
        emit(
            f"sweep/{label}/seed={cell.seed_index}",
            dt * 1e6 / rounds,
            f"status={status};loss={summary['final_loss']:.4f}",
        )

    with contextlib.ExitStack() as stack:
        root = store_dir or stack.enter_context(tempfile.TemporaryDirectory(prefix="sweep-"))
        store = run_sweep(sweep, root, workers=workers, on_cell=on_cell)
        cells_csv, summary_csv = write_collated(store)
        print(f"# collated: {cells_csv}")
        print(f"# collated: {summary_csv}")


def list_registered() -> None:
    """Print every registered name the spec/sweep doors can reach."""
    from repro.core.clustering import CLUSTERERS
    from repro.core.samplers import SAMPLERS
    from repro.fl.engine import ENGINES
    from repro.fl.experiment import DATASETS
    from repro.fl.population import POPULATIONS
    from repro.fl.scheduler import SCHEDULERS
    from repro.kernels.sketch import SKETCHERS

    print("samplers:    " + " ".join(SAMPLERS.names()))
    print("engines:     " + " ".join(ENGINES.names()))
    print("datasets:    " + " ".join(DATASETS.names()))
    print("populations: " + " ".join(POPULATIONS.names()))
    print("clusterers:  " + " ".join(CLUSTERERS.names()))
    print("sketchers:   " + " ".join(SKETCHERS.names()))
    print("schedulers:  " + " ".join(SCHEDULERS.names()))
    print("benchmarks:  " + " ".join(name for name, _ in MODULES))


def main(argv: "list[str] | None" = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--spec", default=None,
        help="experiment-spec JSON (inline or a file path): run that one "
        "declarative scenario instead of the full benchmark sweep",
    )
    ap.add_argument(
        "--sweep", default=None,
        help="sweep-spec JSON (inline or a file path): run a whole campaign "
        "(grid x seeds) through the resumable RunStore and collate it",
    )
    ap.add_argument(
        "--store", default=None,
        help="RunStore directory for --sweep (resumable; ephemeral if omitted)",
    )
    ap.add_argument(
        "--workers", type=int, default=1,
        help="process-pool fan-out for independent --sweep cells",
    )
    ap.add_argument(
        "--list", action="store_true",
        help="print registered samplers / engines / datasets / populations / "
        "clusterers / benchmark modules",
    )
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    enable_compile_cache()
    if args.list:
        list_registered()
        return
    if args.spec and args.sweep:
        ap.error("--spec and --sweep are mutually exclusive")
    if args.spec:
        run_one_spec(args.spec)
        return
    if args.sweep:
        run_one_sweep(args.sweep, args.store, args.workers)
        return
    print("name,us_per_call,derived")
    failures = []
    for name, mod in MODULES:
        t0 = time.time()
        try:
            mod.main()
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
