"""Async re-clustering planner: round wall-time with Algorithm 2's rebuild
on vs off the critical path, and streamed-similarity peak memory vs ``d``.

Section 1 — planner overlap: the same FL run (batched engine, Algorithm 2
sampler) with ``planner="sync"`` pays the O(n²d) distances + O(n³) Ward +
urn filling *inside* every round; ``planner="async"`` hands the rebuild to
a background worker and the round only pays a device scatter + snapshot.
The acceptance target is a lower mean round wall-time for async at
n >= 200 clients on CPU; per-round plan staleness is reported as the mean
``plan_lag_rounds`` (0 for sync by construction).

Section 2 — streamed similarity: the one-shot kernel pads the full (n, d)
block to tile multiples before launching; ``pairwise_distances_streamed``
(now one fused ``pallas_call`` with an in-kernel d-grid) never pads the
full block, so the padded peak stops growing with ``d``. Reported: the
padded-slab peak bytes of each path (exact, from the kernel's block
arithmetic) and wall time.

Section 3 — rebuild at scale: one ``build_plan_algorithm2`` call per
(clusterer, n) cell. At moderate n the three registered clusterers are
compared end-to-end (host ward reference; ``ward_jit`` consuming the fused
streamed kernel's device distances; ``kmeans``). At n=10k clients (full
mode) the host O(n³) Ward is infeasible, so the section reports the
device paths that remain: the jitted k-means rebuild (cold + warm — no
(n, n) matrix at all on this path) and the distance stage alone (host
numpy f64 vs one fused streamed launch).

``--drift`` adds Section 4 — the measured rebuild trigger: the same run
with a fixed ``rebuild_every=1`` cadence vs ``drift_threshold``, reporting
round wall-time, rebuilds actually executed, and the mean assignment-churn
statistic (``RoundRecord.plan_drift``).

Usage (module form — `benchmarks` is a package):
  PYTHONPATH=src python -m benchmarks.bench_async_planner [--smoke] [--drift]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import emit


def _random_clients(n_clients: int, dim: int, per_client: int):
    from repro.data.federated import ClientData, FederatedDataset

    rng = np.random.default_rng(0)
    clients = []
    for _ in range(n_clients):
        x = rng.normal(size=(per_client, dim)).astype(np.float32)
        y = rng.integers(0, 10, size=per_client)
        clients.append(ClientData(x_train=x, y_train=y, x_test=x[:8], y_test=y[:8]))
    return FederatedDataset(clients)


def _register_dataset():
    from repro.fl.experiment import DATASETS

    if "random_clients" not in DATASETS:
        DATASETS.register("random_clients", _random_clients)


def _mean_round_time(dataset, planner: dict, *, m: int, rounds: int, dim: int):
    """(mean s/round after compile warm-up, mean lag, rebuilds, mean drift).

    ``planner`` is the spec's planner section verbatim (mode, cadence or
    drift threshold). Rebuilds counts only post-initial plan builds; drift
    averages the measured ``plan_drift`` telemetry (-1.0 when the run never
    measured drift, i.e. fixed-cadence mode).
    """
    from repro.fl.experiment import build_experiment

    spec = {
        "data": {
            "name": "random_clients",
            "options": {"n_clients": dataset.n_clients, "dim": dim, "per_client": 60},
        },
        "sampler": {"name": "algorithm2", "m": m},
        "planner": dict(planner),
        "train": {
            "n_rounds": rounds, "n_local_steps": 10, "batch_size": 32,
            "lr": 0.05, "seed": 0, "eval_every": 10**9, "hidden": [32],
        },
    }
    # the context manager owns sampler.close() — the async worker used to
    # leak here whenever a run raised between construction and close()
    with build_experiment(spec, dataset=dataset) as srv:
        srv.run_round(0)  # warm-up: engine compile + first rebuild
        t0 = time.perf_counter()
        for t in range(1, rounds + 1):
            srv.run_round(t)
        dt = (time.perf_counter() - t0) / rounds
        lag = float(np.mean(srv.history.series("plan_lag_rounds")[1:]))
        rebuilds = srv.sampler.plan_service.rebuilds_done()
        drifts = [v for v in srv.history.series("plan_drift") if v >= 0]
        drift = float(np.mean(drifts)) if drifts else -1.0
    return dt, lag, rebuilds, drift


def _padded_peak_bytes(n: int, d: int, block_n: int, block_d: int) -> int:
    """Bytes of the padded f32 block a single kernel launch materializes
    (mirrors pairwise_kernel's block arithmetic)."""
    bn = min(block_n, max(8, n))
    bd = min(block_d, max(8, d))
    return (n + (-n % bn)) * (d + (-d % bd)) * 4


def _streamed_sweep(d_values, *, n: int, d_chunk: int, block_n: int, block_d: int):
    from benchmarks.common import timed
    from repro.kernels.similarity.ops import (
        pairwise_distances_device,
        pairwise_distances_streamed,
    )

    rng = np.random.default_rng(1)
    for d in d_values:
        G = rng.normal(size=(n, d)).astype(np.float32)
        one_shot = _padded_peak_bytes(n, d, block_n, block_d)
        streamed = _padded_peak_bytes(n, min(d, d_chunk), block_n, block_d)
        us_one, out_one = timed(
            lambda: np.asarray(
                pairwise_distances_device(
                    G, "arccos", block_n=block_n, block_d=block_d, interpret=True
                )
            ),
            repeats=2,
        )
        us_st, out_st = timed(
            lambda: np.asarray(
                pairwise_distances_streamed(
                    G, "arccos", block_n=block_n, block_d=block_d,
                    d_chunk=d_chunk, interpret=True,
                )
            ),
            repeats=2,
        )
        np.testing.assert_allclose(out_one, out_st, atol=1e-4)
        emit(
            f"similarity_streamed/n={n}/d={d}/one_shot", us_one,
            f"padded_peak={one_shot / 2**20:.2f}MiB",
        )
        emit(
            f"similarity_streamed/n={n}/d={d}/streamed", us_st,
            f"padded_peak={streamed / 2**20:.2f}MiB (chunk={d_chunk}); "
            f"peak_ratio={one_shot / streamed:.1f}x",
        )


def _rebuild_scale(*, smoke: bool) -> None:
    """Section 3: plan-rebuild cost off the training profile.

    Every cell is one :func:`build_plan_algorithm2` call over a synthetic
    gradient block — exactly what the planner's worker executes. At n_big
    the host Ward reference is O(n³) ≈ 10¹² ops and is omitted as
    infeasible; the cells that remain are the device rebuild paths the
    tentpole added.
    """
    from benchmarks.common import timed
    from repro.core.clustering import pairwise_distances
    from repro.core.samplers.algorithm2 import build_plan_algorithm2
    from repro.core.types import ClientPopulation
    from repro.kernels import interpret_mode
    from repro.kernels.similarity.ops import (
        make_distance_fn,
        pairwise_distances_streamed,
    )

    interpret = interpret_mode()
    n_small, n_big, d = (48, 200, 16) if smoke else (512, 10_000, 64)
    m_small, m_big = (5, 5) if smoke else (24, 50)
    rng = np.random.default_rng(0)

    # moderate n: the three registered clusterers end-to-end. ward_jit gets
    # the fused streamed kernel's device distances — the (n, n) matrix and
    # the Lance–Williams loop both stay on device.
    G_small = rng.normal(size=(n_small, d)).astype(np.float32)
    pop_small = ClientPopulation(np.full(n_small, 100))
    device_dist = make_distance_fn(interpret=interpret, streamed=True, as_numpy=False)
    cells = [
        ("ward_host", dict(distance_fn=None, clusterer="ward")),
        ("ward_jit", dict(distance_fn=device_dist, clusterer="ward_jit")),
        ("kmeans", dict(distance_fn=None, clusterer="kmeans")),
    ]
    repeats = 1 if smoke else 2
    for name, kw in cells:
        us, _ = timed(
            lambda kw=kw: build_plan_algorithm2(pop_small, m_small, G_small, **kw),
            repeats=repeats,
        )
        emit(f"plan_rebuild/n={n_small}/{name}", us, "full plan build (warm)")

    # n_big: the off-profile rebuild. kmeans clusters G directly — no (n, n)
    # matrix exists anywhere on this path, so it is the one that scales.
    G_big = rng.normal(size=(n_big, d)).astype(np.float32)
    pop_big = ClientPopulation(np.full(n_big, 100))
    big_build = lambda: build_plan_algorithm2(pop_big, m_big, G_big, clusterer="kmeans")
    us_cold, _ = timed(big_build, repeats=1, warmup=0)
    us_warm, _ = timed(big_build, repeats=repeats)
    emit(f"plan_rebuild/n={n_big}/kmeans_cold", us_cold, "jit compile + build")
    emit(f"plan_rebuild/n={n_big}/kmeans_warm", us_warm, "no (n,n) matrix on this path")

    if not smoke:
        # distance stage alone at n_big: f64 host reference vs one fused
        # streamed launch. host ward on top of it would be O(n^3) — omitted.
        us_host, _ = timed(
            lambda: pairwise_distances(G_big, "arccos"), repeats=1, warmup=0
        )
        emit(
            f"plan_rebuild/n={n_big}/host_distances_only", us_host,
            "f64 numpy O(n^2 d) stage alone; host ward O(n^3) omitted (infeasible)",
        )
        us_fused, _ = timed(
            lambda: np.asarray(
                pairwise_distances_streamed(G_big, "arccos", interpret=interpret)
            ),
            repeats=1, warmup=0,
        )
        emit(
            f"plan_rebuild/n={n_big}/fused_distances", us_fused,
            "one fused streamed launch (interpret mode off-TPU), no padded (n,d) block",
        )


def _drift_section(*, smoke: bool) -> None:
    """Section 4: measured drift trigger vs the fixed rebuild cadence."""
    dim = 16
    n = 40 if smoke else 200
    rounds = 2 if smoke else 6
    dataset = _random_clients(n_clients=n, dim=dim, per_client=60)
    fx_dt, _, fx_rb, _ = _mean_round_time(
        dataset, {"mode": "sync", "rebuild_every": 1}, m=10, rounds=rounds, dim=dim
    )
    threshold = 0.2
    dr_dt, _, dr_rb, drift = _mean_round_time(
        dataset, {"mode": "sync", "drift_threshold": threshold},
        m=10, rounds=rounds, dim=dim,
    )
    emit(
        f"drift_planner/n={n}/fixed", fx_dt * 1e6,
        f"us per round; rebuilds={fx_rb}",
    )
    emit(
        f"drift_planner/n={n}/threshold={threshold}", dr_dt * 1e6,
        f"us per round; rebuilds={dr_rb} mean_drift={drift:.3f}",
    )


def main(argv: "list[str] | None" = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny sizes for CI")
    ap.add_argument(
        "--drift", action="store_true",
        help="also run the drift-triggered planner section",
    )
    # programmatic callers (benchmarks.run) pass no argv and get defaults;
    # parse_args(None) would read the harness's own sys.argv and SystemExit
    args = ap.parse_args([] if argv is None else argv)

    _register_dataset()
    dim = 16
    ns = (40,) if args.smoke else (200, 400)
    rounds = 2 if args.smoke else 6
    for n in ns:
        dataset = _random_clients(n_clients=n, dim=dim, per_client=60)
        secs, lags = {}, {}
        for planner in ("sync", "async"):
            secs[planner], lags[planner], _, _ = _mean_round_time(
                dataset, {"mode": planner}, m=10, rounds=rounds, dim=dim
            )
        speedup = secs["sync"] / secs["async"]
        emit(f"async_planner/n={n}/sync", secs["sync"] * 1e6, "us per round; lag=0")
        emit(
            f"async_planner/n={n}/async", secs["async"] * 1e6,
            f"us per round; speedup={speedup:.2f}x "
            f"mean_lag={lags['async']:.2f} rounds",
        )

    if args.smoke:
        _streamed_sweep((96,), n=24, d_chunk=32, block_n=8, block_d=16)
    else:
        _streamed_sweep((512, 2048, 8192), n=128, d_chunk=512, block_n=128, block_d=128)

    _rebuild_scale(smoke=args.smoke)
    if args.drift:
        _drift_section(smoke=args.smoke)


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
