"""Bring-up check: the served FL path on one TPU chip at the paper's widths.

Drives the normal entry point — ``build_experiment`` + ``FederatedServer.run``,
as ``python -m repro.launch.fl_service`` does — in one process, on the paper's
controlled MNIST setting: ``by_class_shards`` with 784 features, 10 classes ×
10 clients × 500 samples (about 157 MB of staged client data), the 784-50-10
MLP (d = 39,760, a 16 MB gradient store), m = 10, N = 10, B = 50.

Phases, each printing its wall and compile seconds on its own line:

  (a) device    platform, device kind and count; fails unless the platform
                is ``tpu``
  (b) served    Algorithm 2 with the default planner (the fused similarity
                kernel on the 100 × 39,760 store), checkpointing every round;
                then killed and resumed in process: the resumed History must
                equal the uninterrupted one byte for byte (``plan_build_ms``,
                wall-clock telemetry, normalized out)
  (c) sketched  the same spec with the SRP-sketched, k-means, async planner
  (d) reference the fused distances on (b)'s store against the float64 numpy
                reference, the SRP sketch against its host reference, and one
                batched round against the reference per-client loop
                (``repro.fl.client.ReferenceRoundEngine``) from the same state
  (e) kernels   the kernels lowered as compiled Mosaic calls

``--four-chips`` runs only the sharded path and what it is compared with:
the spec of (b) with m = 8 on a ``4x1`` client mesh, against the same spec on
one device, in one process.

Usage::

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a four-chip host

The last line of stdout is ``{"ok": true, "device": {...}}``; a failed phase
exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: The paper's controlled MNIST setting (Fig. 1) and round shape.
PAPER = {
    "n_classes": 10,
    "clients_per_class": 10,
    "train_per_client": 500,
    "test_per_client": 100,
    "dim": 784,
    "hidden": (50,),
    "m": 10,
    "n_local_steps": 10,
    "batch_size": 50,
}
#: Rounds each served run takes, and the round the kill lands after.
ROUNDS = 4
KILL_AT = 2
#: Sketch width of phase (c).
SKETCH_DIM = 64
#: Largest error allowed against a reference, as a fraction of the
#: reference's largest magnitude. TPU f32 matmuls at default precision round
#: their inputs to bf16 (2^-8 relative); this leaves 2.5x headroom over that.
TOL = 1e-2

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while it is open."""

    def __init__(self):
        self.seconds = 0.0

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def run_phase(name: str, fn, *args, **kwargs):
    """Run one phase and print its wall and compile seconds."""
    t0 = time.perf_counter()
    with CompileClock() as clock:
        out = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    print(f"phase {name}: wall_s={wall} compile_s={clock.seconds}", flush=True)
    return out


def spec_dict(size: dict, *, planner=None, mesh_spec=None, m=None) -> dict:
    """The ExperimentSpec dict of a served run at ``size`` (e.g. :data:`PAPER`)."""
    spec = {
        "data": {
            "name": "by_class_shards",
            "options": {
                k: size[k]
                for k in ("n_classes", "clients_per_class", "train_per_client",
                          "test_per_client", "dim")
            },
        },
        "sampler": {"name": "algorithm2", "m": m or size["m"], "seed": 3},
        "engine": {"name": "batched", "mesh_spec": mesh_spec},
        "train": {
            "n_rounds": ROUNDS,
            "n_local_steps": size["n_local_steps"],
            "batch_size": size["batch_size"],
            "hidden": list(size["hidden"]),
            "checkpoint_every": 1,
            "seed": 1,
        },
    }
    if planner is not None:
        spec["planner"] = planner
    return spec


def canonical_history(history) -> str:
    """History JSON with ``plan_build_ms`` (wall-clock telemetry) normalized."""
    recs = json.loads(history.to_json())
    for r in recs:
        r["plan_build_ms"] = -1.0
    return json.dumps(recs)


def scaled_error(got, ref) -> float:
    """max |got - ref| over max |ref|."""
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != reference shape {ref.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def check_close(name: str, got, ref) -> float:
    err = scaled_error(got, ref)
    print(f"  {name}: max error {err} of the reference's scale (tolerance {TOL})", flush=True)
    if not err <= TOL:
        raise AssertionError(f"{name}: error {err} exceeds {TOL}")
    return err


@dataclasses.dataclass
class ServedRun:
    """What later phases read from a served run."""

    spec: object
    history: object
    store: object
    checkpoint: str


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_device(min_count: int = 1) -> dict:
    """(a) The device JAX sees; fails unless it is a TPU with enough chips."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"  device: {json.dumps(info)}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"error: no TPU: JAX's devices are {info['platform']!r}")
    if info["count"] < min_count:
        raise SystemExit(f"error: {info['count']} chips, this path needs {min_count}")
    return info


def _served(spec, dataset, checkpoint: str, *, stop_after=None):
    from repro.fl.experiment import build_experiment

    with build_experiment(spec, dataset=dataset, checkpoint_path=checkpoint) as srv:
        if stop_after is None:
            history = srv.run()
        else:
            history = srv.run(should_stop=lambda: len(srv.history.records) >= stop_after)
    return srv, history


def phase_served(size: dict, out_dir: Path, ds) -> ServedRun:
    """(b) Algorithm 2, default planner; kill after KILL_AT rounds, resume."""
    from repro.fl.experiment import ExperimentSpec, build_experiment

    spec = ExperimentSpec.from_dict(spec_dict(size))
    full_ck = str(out_dir / "served_full.npz")
    srv, full = _served(spec, ds, full_ck)
    (out_dir / "served_history.json").write_text(full.to_json())

    killed_ck = str(out_dir / "served_killed.npz")
    _, part = _served(spec, ds, killed_ck, stop_after=KILL_AT)
    if len(part.records) != KILL_AT:
        raise AssertionError(f"kill landed after {len(part.records)} rounds, not {KILL_AT}")
    with build_experiment(spec, dataset=ds, checkpoint_path=killed_ck) as resumed:
        start = resumed.resume()
        if start != KILL_AT:
            raise AssertionError(f"resumed at round {start}, not {KILL_AT}")
        rest = resumed.run()
    if canonical_history(rest) != canonical_history(full):
        raise AssertionError("resumed History differs from the uninterrupted run")
    losses = [r.train_loss for r in full.records]
    print(f"  {len(full.records)} rounds, losses {losses}; resume after {KILL_AT} "
          "rounds is byte-identical", flush=True)
    return ServedRun(spec, full, srv.sampler.gradient_store.snapshot(), full_ck)


def phase_sketched(size: dict, out_dir: Path, dataset) -> ServedRun:
    """(c) The spec of (b) with the SRP-sketched, k-means, async planner."""
    import numpy as np

    from repro.fl.experiment import ExperimentSpec

    planner = {"sketch": "srp", "sketch_dim": SKETCH_DIM, "clusterer": "kmeans",
               "mode": "async"}
    spec = ExperimentSpec.from_dict(spec_dict(size, planner=planner))
    ck = str(out_dir / "sketched.npz")
    srv, hist = _served(spec, dataset, ck)
    store = srv.sampler.gradient_store
    if store.dim != SKETCH_DIM:
        raise AssertionError(f"sketched store is {store.dim} wide, not {SKETCH_DIM}")
    losses = [r.train_loss for r in hist.records]
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite losses {losses}")
    print(f"  {len(hist.records)} rounds, losses {losses}, store {store.nbytes} bytes",
          flush=True)
    return ServedRun(spec, hist, store.snapshot(), ck)


def phase_reference(served: ServedRun, dataset) -> dict:
    """(d) Each device result against its reference, on the chip."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.clustering import pairwise_distances
    from repro.fl.aggregation import flatten_params
    from repro.fl.client import reference_engine
    from repro.fl.engine import ENGINES, register_engine
    from repro.fl.experiment import build_experiment
    from repro.kernels.similarity.ops import resolve_distance_backend
    from repro.kernels.sketch import SKETCHERS

    errs = {}
    G = served.store
    seen = int((np.abs(np.asarray(G)).sum(axis=1) > 0).sum())
    if seen < 2:
        raise AssertionError(f"only {seen} store rows are filled; nothing to compare")
    fused = resolve_distance_backend("auto")(G, "arccos")
    errs["distances"] = check_close(
        f"fused arccos distances, {G.shape} store ({seen} rows filled)",
        fused, pairwise_distances(np.asarray(G), "arccos"),
    )

    d = G.shape[1]
    sketcher = SKETCHERS.get("srp")(d, SKETCH_DIM, seed=0)
    X = (np.random.default_rng(0).standard_normal((served.spec.sampler.m, d)) * 1e-2).astype(
        np.float32
    )
    errs["srp"] = check_close(
        f"SRP sketch {X.shape} -> {SKETCH_DIM}", sketcher(jnp.asarray(X)),
        sketcher.reference(X),
    )

    # one more round from (b)'s final checkpoint on each engine; the reference
    # loop is no built-in engine, so it is registered for this phase only
    added = "reference" not in ENGINES
    if added:
        register_engine("reference", reference_engine)
    rounds = {}
    try:
        for engine in ("batched", "reference"):
            spec = dataclasses.replace(
                served.spec, engine=dataclasses.replace(served.spec.engine, name=engine)
            )
            with build_experiment(
                spec, dataset=dataset, checkpoint_path=served.checkpoint
            ) as srv:
                t = srv.resume()
                rec = srv.run_round(t)
            rounds[engine] = (rec, np.asarray(flatten_params(srv.params)))
    finally:
        if added:
            ENGINES.unregister("reference")
    (rb, pb), (rr, pr) = rounds["batched"], rounds["reference"]
    if not np.array_equal(rb.agg_weights, rr.agg_weights):
        raise AssertionError("the two engines drew different clients from one state")
    errs["round_params"] = check_close("batched vs reference round, params", pb, pr)
    errs["round_loss"] = check_close(
        "batched vs reference round, train loss", rb.train_loss, rr.train_loss
    )
    return errs


def phase_kernels(served: ServedRun) -> None:
    """(e) The kernels are compiled on the TPU and interpreted elsewhere."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import interpret_mode
    from repro.kernels.similarity.ops import resolve_distance_backend
    from repro.kernels.sketch import SKETCHERS

    interpret = interpret_mode()
    n, d = served.store.shape
    dist = resolve_distance_backend("auto", as_numpy=False)
    sketch = SKETCHERS.get("srp")(d, SKETCH_DIM, seed=0)
    for name, fn, shape in (
        ("similarity", lambda g: dist(g, "arccos"), (n, d)),
        ("srp sketch", lambda x: sketch(x), (served.spec.sampler.m, d)),
    ):
        arg = jax.ShapeDtypeStruct(shape, jnp.float32)
        compiled_kernel = "tpu_custom_call" in jax.jit(fn).lower(arg).as_text()
        if compiled_kernel == interpret:
            raise AssertionError(
                f"{name} kernel: interpret mode {interpret} on "
                f"{jax.default_backend()!r}, but tpu_custom_call present: {compiled_kernel}"
            )
    print(f"  kernels {'interpreted' if interpret else 'compiled (tpu_custom_call)'}",
          flush=True)


def phase_sharded(size: dict, out_dir: Path, dataset, *, m: int, chips: int = 4) -> dict:
    """The spec of (b) on a ``{chips}x1`` client mesh against one device."""
    import numpy as np

    from repro.fl.aggregation import flatten_params
    from repro.fl.experiment import ExperimentSpec

    runs = {}
    for label, mesh_spec in (("one device", None), (f"{chips}x1 mesh", f"{chips}x1")):
        spec = ExperimentSpec.from_dict(spec_dict(size, m=m, mesh_spec=mesh_spec))
        ck = str(out_dir / f"sharded_{mesh_spec or 'single'}.npz")
        srv, hist = _served(spec, dataset, ck)
        staged = srv._engine.per_device_staged_bytes()
        print(f"  {label}: per-device staged bytes {staged}", flush=True)
        runs[label] = (hist, np.asarray(flatten_params(srv.params)), staged)
    (h1, p1, s1), (hm, pm, sm) = runs.values()
    if not sm < s1:
        raise AssertionError(f"the mesh staged {sm} bytes per device, one device {s1}")
    errs = {"params": check_close("mesh vs one device, final params", pm, p1)}
    for r1, rm in zip(h1.records, hm.records):
        if not np.array_equal(r1.agg_weights, rm.agg_weights):
            raise AssertionError(f"round {r1.round}: the mesh drew different clients")
    errs["loss"] = check_close(
        "mesh vs one device, train losses",
        [r.train_loss for r in hm.records], [r.train_loss for r in h1.records],
    )
    errs["acc"] = check_close(
        "mesh vs one device, test accuracy",
        [r.test_acc for r in hm.records], [r.test_acc for r in h1.records],
    )
    return errs


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4x1-mesh path and its one-device comparison")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="directory for checkpoints and histories")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    chips = 4 if args.four_chips else 1
    info = run_phase("a device", phase_device, chips)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    from repro.fl.experiment import ExperimentSpec, build_dataset

    ds = run_phase(
        "data", build_dataset, ExperimentSpec.from_dict(spec_dict(PAPER)).data
    )
    if args.four_chips:
        run_phase("four-chip sharded", phase_sharded, PAPER, out, ds, m=8, chips=chips)
    else:
        served = run_phase("b served", phase_served, PAPER, out, ds)
        run_phase("c sketched", phase_sketched, PAPER, out, ds)
        run_phase("d reference", phase_reference, served, ds)
        run_phase("e kernels", phase_kernels, served)
    device = {"platform": info["platform"], "kind": info["kind"], "count": chips}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
