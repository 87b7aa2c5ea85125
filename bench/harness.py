"""One run of one cell: set-up, a timed window of served FL rounds, the check.

Everything that belongs to a cell is found by name under the benchmark's
root: ``BENCHMARK.json`` names the cell's configuration (its ``file``) and
traffic mix (``mixes/<traffic>.json``); the configuration names its model
kind (``models/<kind>.py``, see :func:`model_kind`); the limits of its check
live in ``limits/<cell>.json`` and each per-layer metric is read by
``layer_metrics/<metric>.py`` (a ``read(ctx)`` that returns a number or
``None``). Adding a cell, or a configuration of another model, adds files
and entries; no code changes.

A run:

1. set-up (``setup_s``, from process start): the model kind's ``build``
   makes the clients' data and the initial weights from the seed and the
   server, by the program's ``build_experiment``; its first
   :func:`rounds_checked` rounds
   through ``FederatedServer.run``, recorded for the check; a warm-up of every shape
   the window can meet (one local-work dispatch per distinct-client count
   ``1..m``, with its update slice and store scatter where the round
   returns rows);
2. the window: the same server's ``run`` until ``seconds`` have passed, stopped
   by its ``should_stop`` hook, one stamp per round, ``block_until_ready`` on
   the global model before the clock is read; with ``trace`` the window runs
   under the profiler with the server's phases wrapped in host spans;
3. the check, after ``memory_peak_bytes`` is read and the server is freed: the
   reference runs as many rounds from the same inputs, building its own
   plans (``reference.py``, with the kind's model half), and ``check.py``
   compares them.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import check
import inputs
import reference
import trace_reduce
from compile_clock import CompileClock

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent


# --------------------------------------------------------------------------
# discovery
# --------------------------------------------------------------------------
class Bench:
    """``BENCHMARK.json`` and the files it names, under ``repo``."""

    def __init__(self, repo: Path = REPO, bench_dir: Path = BENCH_DIR):
        self.repo, self.dir = Path(repo), Path(bench_dir)
        self.spec = json.loads((self.repo / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"error: no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.repo / c["file"]).read_text())
        raise SystemExit(f"error: no config {name!r} in BENCHMARK.json")

    def mix(self, traffic: str) -> dict:
        return json.loads((self.dir / "mixes" / f"{traffic}.json").read_text())

    def limits(self, cell: str) -> dict:
        return json.loads((self.dir / "limits" / f"{cell}.json").read_text())

    def metrics(self, cell: str, kind: str) -> list[dict]:
        """The cell's ``end_to_end`` or ``per_layer`` metric entries."""
        return [m for m in self.spec[kind] if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        return _load(self.dir / "layer_metrics" / f"{metric}.py", "layer_metric_").read


def _load(path: Path, prefix: str):
    """The module in the file at ``path``, named ``prefix`` + its stem."""
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.lru_cache(maxsize=None)
def _kind_module(path: Path):
    return _load(path, "bench_model_")


def model_kind(cfg: dict, bench_dir: Path = BENCH_DIR):
    """The configuration's model kind: the module ``models/<kind>.py`` named by
    its ``"model": {"kind": ...}``; without a ``model`` section, the MLP of
    ``train.hidden``. A kind defines

    * ``build(cfg, mix, seeds) -> (server, reference inputs)``: the clients'
      data, the initial weights, the server (:func:`experiment_dict` and the
      kind's own model) and the reference's inputs
      (:func:`reference_inputs`);
    * ``local_train(dtype)`` and ``evaluate(dtype)``: the reference's model
      half, which :func:`reference.replay` calls;
    * ``shapes(cfg, srv) -> dict``: sizes the readers read, among them
      ``train_flops_per_client``, the model FLOPs of one distinct client's
      local work in a round;
    * optionally ``check_round_count(cfg, n_clients, m)``, the rounds the
      check covers (default :func:`check_round_count`).

    One module per file, loaded once per process.
    """
    kind = cfg.get("model", {}).get("kind", "mlp")
    return _kind_module(Path(bench_dir) / "models" / f"{kind}.py")


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------
def experiment_dict(cfg: dict, mix: dict, seeds: dict) -> dict:
    """The program's ``ExperimentSpec`` of a cell, but for the model: the
    sampler, scheduler and population of the mix, the protocol's ``train``
    sizes of the configuration. A model kind adds its own model."""
    tr = cfg["train"]
    spec = {
        "data": {"name": cfg["data"]["partition"]},
        "sampler": {"name": mix["sampler"]["name"], "m": tr["m"], "seed": seeds["sampler"],
                    "options": mix["sampler"].get("options", {})},
        "engine": {"name": "batched"},
        "train": {"n_rounds": 10**9, "n_local_steps": tr["n_local_steps"],
                  "batch_size": tr["batch_size"], "lr": tr["lr"],
                  "eval_every": mix["eval_every"], "seed": seeds["train"]},
        "population": mix["population"],
        "scheduler": mix["scheduler"],
    }
    if mix.get("planner"):
        spec["planner"] = mix["planner"]
    return spec


def reference_inputs(cfg: dict, mix: dict, seeds: dict, clients: list, params0) -> dict:
    """What :func:`reference.replay` runs from, but for the model kind: the
    clients' data ``(x_train, y_train, x_test, y_test)``, the initial weights
    (a pytree) on the host, the seeds, the plan rule and the protocol's sizes."""
    import jax

    tr = cfg["train"]
    return {
        "clients": clients, "seeds": seeds, "rule": reference.plan_rule(mix),
        "m": tr["m"], "lr": tr["lr"],
        "n_local_steps": tr["n_local_steps"], "batch_size": tr["batch_size"],
        "params0": jax.tree_util.tree_map(np.asarray, params0),
    }


def build_server(cfg: dict, mix: dict, seed: int, *, bench_dir: Path = BENCH_DIR):
    """(server, the reference's inputs), all made from ``seed`` by the
    configuration's model kind; the inputs hold the kind under ``"kind"``."""
    kind = model_kind(cfg, bench_dir)
    srv, ref_inputs = kind.build(cfg, mix, inputs.derive_seeds(seed))
    return srv, dict(ref_inputs, kind=kind)


class Capture:
    """Records what the server's first rounds produce, through wrappers on
    the built instances' attributes; :meth:`close` removes them.

    The global model is copied to the host twice, after the first round and
    at :meth:`close`, and kept only as the per-leaf norms of its change from
    ``params0`` (the initial weights, on the host). A round's rows are kept,
    in the dtype the engine returns them in, where the round returns them."""

    def __init__(self, srv, params0):
        self.srv = srv
        self.params0 = reference.named_leaves(params0)
        self.clients, self.plans, self.rows = [], [], []
        self.loss, self.acc, self.dist = [], [], []
        self.update_norms = self.change_norms = None
        self.store = None  # the gradient store after the last round, on the host
        self._model = None  # the last round's global model, on the device
        self._undo = []
        self._wrap(srv.sampler, "sample", self._sample)
        self._wrap(srv, "_phase_local_work", self._local_work)
        if getattr(srv.sampler, "_distance_fn", None) is not None:
            self._wrap(srv.sampler, "_distance_fn", self._distances)

    def _wrap(self, obj, attr, hook):
        orig, own = getattr(obj, attr), attr in vars(obj)
        setattr(obj, attr, lambda *a, **k: hook(orig, *a, **k))
        self._undo.append((obj, attr, orig, own))

    def _sample(self, orig, *a, **k):
        res = orig(*a, **k)
        self.clients.append(np.array(res.clients))
        self.plans.append(np.array(self.srv.sampler.plan.r))
        return res

    def _local_work(self, orig, distinct, *a, **k):
        out = orig(distinct, *a, **k)
        if self._model is None:
            self.update_norms = self._norms(out[0])
        self._model = out[0]
        if out[1] is not None:
            self.rows.append(dict(zip(map(int, distinct), np.asarray(out[1]))))
        return out

    def _norms(self, model) -> dict:
        return reference.leaf_norms(reference.named_leaves(model), self.params0)

    def _distances(self, orig, G, measure):
        out = orig(G, measure)
        self.dist.append(np.asarray(out))
        return out

    def on_round(self, rec) -> None:
        self.loss.append(rec.train_loss)
        self.acc.append(rec.test_acc)

    def close(self) -> None:
        if self._model is not None:
            self.change_norms = self._norms(self._model)
            self._model = None
        store = getattr(self.srv.sampler, "gradient_store", None)
        if store is not None:
            self.store = store.asnumpy()
        for obj, attr, orig, own in reversed(self._undo):
            if own:
                setattr(obj, attr, orig)
            else:
                delattr(obj, attr)
        self.srv = None


def check_round_count(n_clients: int, m: int) -> int:
    """Rounds the check covers: ``2 n / m``, by which Algorithm 2's store holds
    most clients' representative gradients (78-87 % of the rows at the two
    configurations' fleets), so the angles and the plan are checked at about
    the fill the window runs at."""
    return 2 * math.ceil(n_clients / m)


def rounds_checked(kind, cfg: dict, n_clients: int) -> int:
    """The rounds the check covers: the kind's own count, or :func:`check_round_count`."""
    m = cfg["train"]["m"]
    own = getattr(kind, "check_round_count", None)
    return own(cfg, n_clients, m) if own is not None else check_round_count(n_clients, m)


def check_rounds(srv, k: int, params0) -> Capture:
    """Drive the server's first ``k`` rounds through its ``run``, recorded;
    ``params0`` is the model the server starts from, on the host."""
    cap = Capture(srv, params0)
    srv.run(on_round=cap.on_round, should_stop=lambda: len(cap.loss) >= k)
    cap.close()
    return cap


def warm_up(srv) -> None:
    """Compile what a round with ``c`` distinct clients runs, for every
    ``c = 1..m``: the local-work dispatch and, where the round returns rows,
    its ``updates[:c]`` slice, the boolean gather of the kept rows and the
    store scatter. The server's model and store are left as they were."""
    import jax

    store = getattr(srv.sampler, "gradient_store", None)
    for c in range(1, srv.sampler.m + 1):
        ids = np.arange(c)
        updates, losses = srv._phase_local_work(ids, np.full(c, 1.0 / c), 0.0)[1:]
        if updates is None:
            jax.block_until_ready(losses)
            continue
        kept = updates[np.ones(c, bool)]
        if store is not None:
            before = store.snapshot()
            store.update(ids, kept)
            store.load(before)
        jax.block_until_ready(kept)


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------
SPANS = (("run_round", "round"), ("_phase_draw", "draw"), ("_phase_local_work", "local_work"))


def wrap_spans(srv) -> None:
    """Host spans around the server's phases (traced runs only)."""
    import jax
    from jax.profiler import TraceAnnotation

    def span(obj, attr, name, block=False):
        orig = getattr(obj, attr)

        def wrapped(*a, **k):
            with TraceAnnotation(name):
                out = orig(*a, **k)
                return jax.block_until_ready(out) if block else out

        setattr(obj, attr, wrapped)

    for attr, name in SPANS:
        span(srv, attr, name)
    span(srv.sampler, "observe_updates", "observe")
    span(srv, "acc_fn", "eval", block=True)


class GcClock:
    """Python's garbage collections while open, as ``(start, seconds, generation)``."""

    def __enter__(self):
        self.events, self._start = [], None
        gc.callbacks.append(self._on_gc)
        return self

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.events.append((self._start, now - self._start, info["generation"]))

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)


def run_window(srv, seconds: float, *, trace_dir: str | None = None) -> dict:
    """Rounds until ``seconds`` have passed; stamps, compile count, collections, trace."""
    import jax
    from jax import profiler

    stamps: list[float] = []
    clock = time.perf_counter
    window = contextlib.nullcontext()
    if trace_dir is not None:
        wrap_spans(srv)
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        profiler.start_trace(trace_dir, profiler_options=opts)
        window = profiler.TraceAnnotation("window")
    with CompileClock() as compiles, GcClock() as collections:
        t0 = clock()
        end = t0 + seconds
        with window:
            srv.run(on_round=lambda _: stamps.append(clock()),
                    should_stop=lambda: clock() >= end)
            jax.block_until_ready(srv.params)
        t1 = clock()
    if trace_dir is not None:
        profiler.stop_trace()
    return {"start": t0, "end": t1, "stamps": stamps, "compiles": compiles.compiles,
            "compile_s": compiles.seconds, "gc": collections.events}


def stalls(win: dict, top: int = 3) -> list[tuple[float, float]]:
    """The ``top`` longest rounds of a window: ``(ms, ms of garbage collection in it)``."""
    edges = [win["start"]] + win["stamps"]
    rounds = sorted(zip(edges[:-1], edges[1:]), key=lambda r: r[0] - r[1])[:top]
    return [((b - a) * 1e3, sum(s for t, s, _ in win["gc"] if a <= t < b) * 1e3)
            for a, b in rounds]


def end_to_end(win: dict) -> dict:
    """``round_ms`` (all the window's time over its rounds) and ``round_p95_ms``."""
    n = len(win["stamps"])
    intervals = np.diff([win["start"]] + win["stamps"])
    return {
        "round_ms": (win["end"] - win["start"]) / n * 1e3,
        "round_p95_ms": float(np.percentile(intervals, 95)) * 1e3,
    }


# --------------------------------------------------------------------------
# per-layer metrics
# --------------------------------------------------------------------------
class MetricContext:
    """What a ``layer_metrics/<name>.py`` reader reads."""

    def __init__(self, trace, records, shapes: dict, peaks: dict):
        self.trace = trace
        self.summary = trace_reduce.device_summary(trace)
        self.spans = trace_reduce.span_seconds(trace)
        self.program = trace_reduce.program_spans(trace)
        self.records = records
        self.rounds = len(records)
        self.shapes = shapes
        self.peaks = peaks

    def _ms_per_round(self, seconds):
        if not seconds or not self.rounds:
            return None
        return sum(seconds) / self.rounds * 1e3

    def span_ms_per_round(self, name: str):
        """Total seconds of a harness span over the window's rounds, in ms per round."""
        return self._ms_per_round(self.spans.get(name))

    def program_ms_per_round(self, name: str):
        """Total seconds of a program span (``fl.*``) over the window's rounds,
        in ms per round."""
        return self._ms_per_round([s for s, _ in self.program.get(name, ())])

    def arg_totals(self, name: str) -> dict:
        """The numeric arguments of a program span, each summed over the
        window's spans of that name: ``{key: sum}``."""
        totals: dict = {}
        for _, args in self.program.get(name, ()):
            for key, value in args.items():
                totals[key] = totals.get(key, 0) + value
        return totals

    def device_seconds(self, fragment: str, *, modules: bool):
        """Summed device time of programs or operations named with ``fragment``."""
        events = trace_reduce.program_events(self.trace, fragment, modules=modules)
        return (sum(e - s for s, e in events) * 1e-9, len(events)) if events else (None, 0)


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"error: no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def shapes_of(kind, cfg: dict, srv) -> dict:
    """The sizes the readers work operations and bytes out from: the
    protocol's and the store's, and the model kind's own."""
    store = getattr(srv.sampler, "gradient_store", None)
    return {
        "n_local_steps": cfg["train"]["n_local_steps"],
        "batch_size": cfg["train"]["batch_size"],
        "store": None if store is None else (store.n_clients, store.dim),
        **kind.shapes(cfg, srv),
    }


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


#: ``trace_reduce.collect``'s selectors of the device's lines in a trace.
TRACE_LINES: dict = {}


def run_cell(bench: Bench, name: str, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    cell = bench.cell(name)
    cfg, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    limits = bench.limits(name)
    dev = device_info()

    srv, ref_inputs = build_server(cfg, mix, seed, bench_dir=bench.dir)
    kind = ref_inputs["kind"]
    n_checked = rounds_checked(kind, cfg, len(srv.dataset.clients))
    cap = check_rounds(srv, n_checked, ref_inputs["params0"])
    warm_up(srv)
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s} s")

    with tempfile.TemporaryDirectory(prefix="fedbench-trace-") as tdir:
        win = run_window(srv, seconds, trace_dir=tdir if trace else None)
        rounds = len(win["stamps"])
        log(f"window: rounds={rounds} compiles={win['compiles']} "
            f"compile_s={win['compile_s']} seconds={win['end'] - win['start']}")
        log(f"collections: gc={len(win['gc'])} "
            f"gen2={sum(g == 2 for _, _, g in win['gc'])} "
            f"gc_s={sum(s for _, s, _ in win['gc'])} longest rounds (ms, gc ms): {stalls(win)}")
        dev["memory_peak_bytes"] = memory_peak()
        result = {"correct": False, "attempted": rounds, "failed": 0}
        units = {m["name"]: m["unit"] for m in bench.metrics(name, "end_to_end")
                 + bench.metrics(name, "per_layer")}
        if trace:
            from jax.profiler import ProfileData

            profile = ProfileData.from_file(trace_reduce.find_xplane(tdir))
            tr = trace_reduce.collect(profile, **TRACE_LINES)
            ctx = MetricContext(tr, srv.history.records[-rounds:], shapes_of(kind, cfg, srv),
                                load_peaks(dev["kind"]))
            values = {}
            for m in bench.metrics(name, "per_layer"):
                v = bench.reader(m["name"])(ctx)
                if v is not None:
                    values[m["name"]] = v
            dev["busy_s"] = ctx.summary["busy_s"]
            dev["window_s"] = ctx.summary["window_s"]
            breakdown = {"device_ops": ctx.summary["device_ops"],
                         "idle_gaps": ctx.summary["idle_gaps"]}
        else:
            values = end_to_end(win)
            values["setup_s"] = setup_s
            breakdown = None
    srv.close()
    del srv
    gc.collect()

    t_check = time.perf_counter()
    prog = check.program_record(cap)
    ref = reference.replay(ref_inputs, n_checked, rows=bool(prog["rows"]))
    ok, table = check.verdict(check.numbers(prog, ref), limits)
    log(f"check: {n_checked} rounds in {time.perf_counter() - t_check} s")
    result["correct"] = ok
    result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    result["device"] = dev
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = table
    return result


def finite(obj):
    """``obj`` with every float that is not finite replaced by its name as a string."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return str(obj)
    return obj

