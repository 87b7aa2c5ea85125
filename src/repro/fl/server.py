"""The FL orchestrator: sample → local work → unbiased aggregation.

Faithful to the paper's protocol:
  * each round, the sampler draws ``l_1..l_m`` (with multiplicity);
  * only the *distinct* sampled clients do local work (a client drawn twice
    trains once and carries weight 2/m — MD/clustered semantics);
  * aggregation is the realized weighted sum (eq. 3/4);
  * similarity-based samplers get the representative gradients
    ``θ_i^{t+1} - θ^t`` of the sampled clients after the round
    (Algorithm 2 line 1's input), never raw data. With the batched engine
    those updates are a *device* array feeding the sampler's gradient store
    by scatter — no per-round host copy; with ``planner="async"`` samplers
    the plan rebuild they trigger overlaps the next round's local work, and
    each ``RoundRecord`` carries ``plan_version`` / ``plan_lag_rounds``.

One round path: the engine named by ``FLConfig.engine`` (an ``ENGINES``
name; ``"batched"``, the only one built in) runs each round's local work and
aggregation. The batched engine runs the whole round as one jitted
vmap-over-clients step (:mod:`repro.fl.engine`), with client data on device
for the entire run. The per-client loop
(:class:`repro.fl.client.ReferenceRoundEngine`) is the tests' numerics
reference, registered by the tests, not a choice a config can make;
``tests/test_round_engine.py`` pins the two together to fp32 tolerance.

Continuous service (the churn-tolerant path): a
:class:`~repro.fl.population.PopulationProcess` turns the fixed-n batch
loop into a long-running service. Each round runs as named phases —

  availability mask → draw → drop resolution → local work + aggregate
  → observe → eval → record

— where the sampler conditions its draw on the round's availability mask
(re-normalized urns, unbiased over the available set), a client that
vanishes mid-round becomes a zero-weight slot in the engine's padded slot
axis with its eq. 3 mass falling back on the current global model, and
``EmptyRoundError`` fires only when *all* realized mass is gone. Crash
tolerance: :meth:`FederatedServer.checkpoint` bundles the full
``ServerState`` (params + server/sampler rng bit-generator state + plan
matrices + gradient store + history cursor) through :mod:`repro.checkpoint`
on a ``checkpoint_every`` cadence, and :meth:`FederatedServer.resume`
reconstructs it so a killed service continues **bit-identically** to an
uninterrupted run (pinned in ``tests/test_service_resume.py``; for
``planner="async"`` the checkpoint first forces the sync fixed point).

Tracing: every round is a ``jax.profiler.StepTraceAnnotation`` named
``fl.round`` (``step_num`` = the round), and each phase above a
``TraceAnnotation`` inside it: ``fl.availability``, ``fl.draw``,
``fl.resolve``, ``fl.local_work`` (the batched engine adds
``fl.local_work.prep`` / ``.dispatch`` / ``.wait``), ``fl.observe``
(with ``fl.observe.gather`` only in a round where someone dropped or was
late: the kept rows' gather; when every participant contributed, the
engine's rows go to the sampler as they are), ``fl.eval`` (with
``fl.eval.run``, the compiled accuracy call) and ``fl.record``. The test
set is copied to the device once, when the server is built, under
``fl.eval.stage`` outside any round; no round copies it.
The spans land in a profiler trace on the device trace's clock when one is
active (``jax.profiler.trace``) and cost about a microsecond each
otherwise; none blocks or moves data. Counters ride as arguments:
``bytes`` (host-to-device bytes) on ``fl.eval.stage`` and
``fl.local_work.dispatch``, ``distinct`` and ``slots`` on
``fl.local_work.prep``, ``rows`` (the kept rows) on ``fl.observe.gather``.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.samplers.base import ClientSampler
from repro.data.federated import FederatedDataset
from repro.fl.engine import ENGINES
from repro.fl.history import History, RoundRecord
from repro.fl.population import PopulationProcess
from repro.launch.mesh import resolve_fl_mesh
from repro.models.simple import accuracy, classification_loss
from repro.optim.base import Optimizer


@dataclasses.dataclass
class FLConfig:
    n_rounds: int = 100
    n_local_steps: int = 50  # N in the paper
    batch_size: int = 50  # B in the paper
    fedprox_mu: float = 0.0
    eval_every: int = 1
    seed: int = 0
    engine: str = "batched"  # any repro.fl.engine.ENGINES name
    # Mesh for the batched engine's client axis: None (single-device,
    # default), "auto" (all local devices on "data"), "DxM" / (D, M) host
    # mesh shapes, or a jax.sharding.Mesh. See repro.launch.mesh.
    # resolve_fl_mesh and the engine module docstring.
    mesh_spec: "str | tuple[int, int] | None" = None
    # Crash tolerance: every `checkpoint_every` completed rounds (and on a
    # service stop request) the full ServerState bundle is written to
    # `checkpoint_path` through repro.checkpoint. 0 / None disables.
    checkpoint_every: int = 0
    checkpoint_path: Optional[str] = None


class EmptyRoundError(ValueError):
    """The sampler produced nothing to aggregate for a round: zero distinct
    clients, or distinct clients whose realized weights sum to zero."""


class FederatedServer:
    def __init__(
        self,
        dataset: FederatedDataset,
        sampler: ClientSampler,
        init_params,
        optimizer: Optimizer,
        config: FLConfig,
        loss_fn: Callable = classification_loss,
        acc_fn: Callable = accuracy,
        population: Optional[PopulationProcess] = None,
        scheduler=None,
        availability=None,
    ):
        """``scheduler`` (a :class:`~repro.fl.scheduler.RoundScheduler`,
        optional) makes the round-closing rule pluggable — None keeps the
        legacy synchronous round exactly. ``availability`` (an
        :class:`~repro.fl.availability.AvailabilityTracker`, optional) folds
        each round's mask + participant outcomes into per-client presence
        scores; attach it to the sampler too
        (``StoreBackedSampler.attach_availability``) to restrict plan
        rebuilds to the recently-seen fleet. Both checkpoint inside
        ``ServerState`` when present. The global test set is staged on the
        device here and stays there; ``acc_fn`` is jitted over it."""
        engine_factory = ENGINES.get(config.engine)  # precise unknown-name error
        self.dataset = dataset
        self.sampler = sampler
        self.params = init_params
        self.opt = optimizer
        self.cfg = config
        self.loss_fn = loss_fn
        # one compiled program over the staged test set; an attribute, so a
        # caller can still swap the function (or wrap it) after construction
        self.acc_fn = jax.jit(acc_fn)
        self.population = population
        self.scheduler = scheduler
        self.availability = availability
        self._rng = np.random.default_rng(config.seed)
        self.history = History()
        # classes each client can contribute — O(total samples) once, so the
        # per-round distinct-class count is a union of tiny class sets
        self._client_classes = [np.unique(c.y_train) for c in dataset.clients]
        mesh = resolve_fl_mesh(config.mesh_spec)
        # the scheduler owns the engine's padded slot count (all built-ins
        # keep it at m — overselection thins at draw time — but the contract
        # lets a custom scheduler stage wider rounds)
        slots = (
            sampler.m if scheduler is None else int(scheduler.required_slots(sampler.m))
        )
        self._engine = engine_factory(dataset, slots, config, mesh)
        self._stage_test_set(mesh)
        # service cursor: the next round to run. run()/resume() maintain it so
        # a restored server continues exactly where the checkpoint left off.
        self._start_round = 0
        self._round_cursor = 0
        self._closed = False

    def _stage_test_set(self, mesh) -> None:
        """Copy the global test set to the device once, for the server's life.

        With no mesh it lands on the default device; on a mesh it is
        replicated, as the round step's global params are, so eval moves no
        data in any round. It stays resident beside the engine's staged
        client data.
        """
        x, y = self.dataset.global_test()
        if mesh is None:
            place = jnp.asarray
        else:
            place = lambda a: jax.device_put(a, NamedSharding(mesh, P()))
        # the ``bytes`` counter is what the copy moves, in the dtypes it gives
        self._test_bytes = sum(
            a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize for a in (x, y)
        )
        with TraceAnnotation("fl.eval.stage", bytes=self._test_bytes):
            self._x_test, self._y_test = place(x), place(y)

    # -- round phases --------------------------------------------------------
    # run_round = availability → draw → drop resolution → local work +
    # aggregate → observe → eval → record. The phases are separate methods
    # so the continuous service's failure points are named and individually
    # testable. Drop resolution happens *before* engine dispatch because the
    # engine fuses local work and aggregation into one jitted step: a
    # dropped client still occupies its padded slot (stable shapes, stable
    # rng stream) but its aggregation weight is zeroed and its mass falls
    # back on the current global model (eq. 3's stale term) — exactly "the
    # device computed, the result never arrived".

    def _phase_availability(self, t: int) -> tuple[Optional[np.ndarray], int]:
        """(mask, n_available); (None, -1) without a population process."""
        if self.population is None:
            return None, -1
        mask = self.population.available_mask(t)
        n_avail = int(mask.sum())
        if n_avail == 0:
            raise EmptyRoundError(
                f"round {t}: availability mask admits zero of "
                f"{self.population.n_clients} clients — nobody can be drawn"
            )
        return mask, n_avail

    def _phase_draw(self, t: int, available: Optional[np.ndarray]):
        """Sampler draw conditioned on availability; fails on empty draws."""
        if self.scheduler is not None:
            # the scheduler owns the draw shape (overselection draws
            # m·(1+β) and thins); its base draw is exactly the legacy call
            result = self.scheduler.draw(t, self.sampler, available)
        else:
            # no mask → the legacy one-argument call, so custom samplers
            # written before availability conditioning keep working untouched
            result = (
                self.sampler.sample(t)
                if available is None
                else self.sampler.sample(t, available)
            )
        # sample() is the round boundary where planner-backed samplers swap
        # in the freshest completed plan — capture what this round drew from
        plan_version, plan_lag = self.sampler.plan_telemetry()
        distinct = result.unique_clients
        if distinct.size == 0:
            raise EmptyRoundError(
                f"round {t}: sampler {type(self.sampler).__name__} returned zero "
                "distinct clients — the plan has no mass anywhere"
                + (" on the available set" if available is not None else "")
                + "; nothing to train or aggregate"
            )
        weights = result.agg_weights[distinct]
        if weights.sum() <= 0:
            raise EmptyRoundError(
                f"round {t}: realized aggregation weights of the {distinct.size} "
                "distinct clients sum to zero — aggregating (and averaging the "
                "round loss) over them is undefined"
            )
        return result, distinct, weights, plan_version, plan_lag

    def _phase_drop_resolution(
        self,
        t: int,
        distinct: np.ndarray,
        weights: np.ndarray,
        stale_weight: float,
        late: Optional[np.ndarray] = None,
    ) -> tuple[np.ndarray, float, np.ndarray]:
        """Zero dropped participants' weights; their mass goes stale.

        Returns ``(weights, stale_weight, dropped)`` — ``dropped`` is the
        boolean mask over ``distinct``. Raises :class:`EmptyRoundError` when
        every realized participant dropped (all realized mass is gone) —
        unless ``late`` marks scheduler-resolved stragglers among the
        survivors: their updates are merely delayed (harvested next round),
        so a round that lost all its mass to *lateness* proceeds as a
        stale-only aggregation instead of dying.
        """
        if self.population is None:
            return weights, stale_weight, np.zeros(distinct.shape, dtype=bool)
        dropped = self.population.dropout_mask(t, distinct)
        if not dropped.any():
            return weights, stale_weight, dropped
        live = weights[~dropped].sum()
        if live <= 0 and not (late is not None and (late & ~dropped).any()):
            raise EmptyRoundError(
                f"round {t}: all {distinct.size} realized participants dropped "
                "mid-round (or the survivors carry zero weight) — every bit of "
                "realized aggregation mass is gone; nothing arrived to aggregate"
            )
        # the aggregation is a plain weighted sum (no re-normalization), so a
        # dropped client's ω_i must land somewhere: it falls back on the
        # current global model, the same eq. 3 stale term uniform sampling uses
        stale_weight = float(stale_weight + weights[dropped].sum())
        weights = np.where(dropped, 0.0, weights)
        return weights, stale_weight, dropped

    def _phase_local_work(self, distinct, weights, stale_weight):
        """Local training + aggregation — one fused engine dispatch."""
        return self._engine.run_round(
            self.params,
            distinct,
            weights,
            stale_weight,
            self._rng,
            self.loss_fn,
            self.opt,
            self.cfg.fedprox_mu,
        )

    def _phase_eval(self, t: int) -> float:
        """Test accuracy of the current model; NaN off the ``eval_every`` cadence."""
        if t % self.cfg.eval_every:
            return float("nan")
        with TraceAnnotation("fl.eval"), TraceAnnotation("fl.eval.run"):
            return float(self.acc_fn(self.params, self._x_test, self._y_test))

    def run_round(self, t: int) -> RoundRecord:
        with TraceAnnotation("fl.availability"):
            available, n_available = self._phase_availability(t)
            # scheduler prologue: flush last round's harvested straggler
            # updates into the gradient store *before* this round draws from it
            n_harvested = (
                int(self.scheduler.begin_round(t, self.sampler))
                if self.scheduler is not None
                else 0
            )
        with TraceAnnotation("fl.draw"):
            result, distinct, weights, plan_version, plan_lag = self._phase_draw(
                t, available
            )
        with TraceAnnotation("fl.resolve"):
            stale_weight = result.stale_weight
            if self.scheduler is not None:
                # round-closing rule: mark stragglers late (weight → stale
                # term, update harvested below) before mid-round drops resolve
                weights, stale_weight, late = self.scheduler.resolve(
                    t, distinct, weights, stale_weight
                )
            else:
                late = np.zeros(distinct.shape, dtype=bool)
            weights, stale_weight, dropped = self._phase_drop_resolution(
                t, distinct, weights, stale_weight, late=late
            )
            n_dropped = int(dropped.sum())
            # a participant that both straggled and crashed is a crash: the
            # result never arrived, so there is nothing to harvest either
            late = late & ~dropped
            n_late = int(late.sum())

        with TraceAnnotation("fl.local_work"):
            self.params, updates_flat, losses = self._phase_local_work(
                distinct, weights, stale_weight
            )

        with TraceAnnotation("fl.observe"):
            if n_late and self.scheduler is not None:
                # harvest: late updates were computed (the engine ran their
                # padded slots) — buffer host copies for next round's store
                self.scheduler.collect(
                    t, distinct[late], updates_flat[np.asarray(late)]
                )

            # observe: feed representative gradients back (Algorithm 2's
            # input) — on-time survivors only; a dropped client's update never
            # reached the server and a straggler's arrives next round via the
            # harvest path, so neither refreshes the similarity state here
            keep = ~(dropped | late)
            contributing = distinct[keep]
            if contributing.size:
                # when everyone contributed, the engine's rows are the kept
                # rows: they go over as they are (no gather, copy or transfer)
                rows = updates_flat
                if not keep.all():
                    with TraceAnnotation("fl.observe.gather", rows=int(contributing.size)):
                        rows = updates_flat[np.asarray(keep)]
                self.sampler.observe_updates(contributing, rows)

            # rebuild-cost telemetry is read *after* observe_updates: the
            # drift statistic (and any sync rebuild) happens there
            plan_build_ms, plan_drift = self.sampler.plan_cost_telemetry()

        # the record is built on both sides of eval, in the round's order
        with TraceAnnotation("fl.record"):
            # availability fold: the mask plus this round's graded outcomes —
            # on-time 1.0, late late_credit, crashed 0.0 (see fl.availability)
            if self.availability is not None:
                self.availability.update(
                    available,
                    on_time=contributing,
                    late=distinct[late],
                    crashed=distinct[dropped],
                )
                avail_score_min = self.availability.min_score()
            else:
                avail_score_min = -1.0

            classes = (
                np.unique(
                    np.concatenate([self._client_classes[int(c)] for c in contributing])
                )
                if contributing.size
                else np.empty(0, np.int64)
            )
        test_acc = self._phase_eval(t)
        with TraceAnnotation("fl.record"):
            agg_weights = result.agg_weights
            if n_dropped or n_late:
                agg_weights = np.array(agg_weights, dtype=np.float64, copy=True)
                agg_weights[distinct[dropped | late]] = 0.0
            live_mass = float(weights.sum())
            rec = RoundRecord(
                round=t,
                # dropped/late participants carry zero weight, so the round loss
                # averages over on-time survivors only; a round that lost every
                # participant to lateness aggregated stale-only mass — no loss
                train_loss=(
                    float(np.average(losses, weights=weights))
                    if live_mass > 0
                    else float("nan")
                ),
                test_acc=test_acc,
                n_distinct_clients=len(distinct),
                n_distinct_classes=len(classes),
                agg_weights=agg_weights,
                plan_version=plan_version,
                plan_lag_rounds=plan_lag,
                plan_build_ms=plan_build_ms,
                plan_drift=plan_drift,
                n_available=n_available,
                n_dropped=n_dropped,
                # n_late also counts draws the scheduler discarded at draw time
                # (overselection surplus); round_status tracks actual stragglers
                # and crashes only — planned surplus is not degradation
                n_late=n_late
                + (self.scheduler.n_late_extra() if self.scheduler is not None else 0),
                n_harvested=n_harvested,
                avail_score_min=avail_score_min,
                round_status="degraded" if (n_dropped or n_late) else "ok",
            )
            self.history.append(rec)
        self._round_cursor = t + 1
        return rec

    def run(
        self,
        on_round: Optional[Callable[[RoundRecord], None]] = None,
        *,
        should_stop: Optional[Callable[[], bool]] = None,
        skip_empty: bool = False,
    ) -> History:
        """Run rounds ``[start, n_rounds)``; returns the full :class:`History`.

        ``start`` is 0 for a fresh server and the checkpointed cursor after
        :meth:`resume`. ``on_round`` is the streaming telemetry hook: called
        with each :class:`RoundRecord` as it lands, so benchmarks/examples
        consume records as the run progresses instead of re-implementing
        collection.

        Service semantics: with ``FLConfig.checkpoint_every > 0`` (and a
        ``checkpoint_path``) the full server state is checkpointed on that
        cadence of completed rounds. ``should_stop`` is polled after each
        round — a SIGTERM-style stop flag; when it trips, a final checkpoint
        is written and the loop exits cleanly. ``skip_empty=True`` converts
        :class:`EmptyRoundError` rounds (everyone offline / everyone dropped)
        into placeholder ``round_status="empty"`` records instead of raising
        — a long-running service rides out a dead fleet; a batch experiment
        should still fail loudly.
        """
        cfg = self.cfg
        every = int(cfg.checkpoint_every or 0)
        for t in range(self._start_round, cfg.n_rounds):
            with StepTraceAnnotation("fl.round", step_num=t):
                try:
                    rec = self.run_round(t)
                except EmptyRoundError:
                    if not skip_empty:
                        raise
                    n_avail = (
                        int(self.population.available_mask(t).sum())
                        if self.population is not None
                        else -1
                    )
                    rec = RoundRecord(
                        round=t,
                        train_loss=float("nan"),
                        test_acc=float("nan"),
                        n_distinct_clients=0,
                        n_distinct_classes=0,
                        n_available=n_avail,
                        round_status="empty",
                    )
                    self.history.append(rec)
                    self._round_cursor = t + 1
            if on_round is not None:
                on_round(rec)
            if every and cfg.checkpoint_path and (t + 1) % every == 0:
                self.checkpoint()
            if should_stop is not None and should_stop():
                if cfg.checkpoint_path:
                    self.checkpoint()
                break
        return self.history

    # -- crash tolerance -----------------------------------------------------
    # ServerState = params + server rng + sampler state (rng, plan matrices,
    # gradient store, plan version/history cursor) + round history. Arrays
    # ride in the checkpoint's .npz pytree; JSON-shaped state (rng
    # bit-generator dicts, the history records) rides in its `extra`
    # side-channel. The population process is deliberately absent: its masks
    # are pure functions of (seed, t), so a resumed server replays the
    # identical availability/dropout trajectory for free.

    def _state_tree(self) -> dict:
        tree = {"params": self.params, "sampler": self.sampler.state_arrays()}
        # optional subsystems checkpoint as their own sections, present only
        # when attached — a scheduler-free server's bundle is unchanged, and
        # restoring a bundle into a differently-configured server fails on
        # the missing/extra key instead of silently dropping state
        if self.scheduler is not None:
            tree["scheduler"] = self.scheduler.state_arrays()
        if self.availability is not None:
            tree["availability"] = self.availability.state_arrays()
        return tree

    def checkpoint(self, path: Optional[str] = None) -> str:
        """Write the full ServerState bundle; returns the path written.

        ``path`` defaults to ``FLConfig.checkpoint_path``. The sampler is
        quiesced first (:meth:`ClientSampler.prepare_state` — async planners
        flush their in-flight rebuild to the sync fixed point), so the
        bundle is always a consistent cut.
        """
        from repro.checkpoint import save_checkpoint

        path = path or self.cfg.checkpoint_path
        if not path:
            raise ValueError(
                "no checkpoint path: pass one or set FLConfig.checkpoint_path"
            )
        self.sampler.prepare_state()
        extra = {
            "server_rng": self._rng.bit_generator.state,
            "sampler": self.sampler.state_meta(),
            "history": json.loads(self.history.to_json()),
        }
        if self.scheduler is not None:
            extra["scheduler"] = self.scheduler.state_meta()
        if self.availability is not None:
            extra["availability"] = self.availability.state_meta()
        save_checkpoint(path, self._state_tree(), step=self._round_cursor, extra=extra)
        return path

    def resume(self, path: Optional[str] = None) -> int:
        """Reconstruct mid-campaign state from a :meth:`checkpoint` bundle.

        Restores params, server rng, the sampler's full state and the round
        history, and positions :meth:`run` at the checkpointed cursor.
        Returns the round the server will run next. For deterministic
        (sync/static-plan) samplers the continuation is bit-identical to the
        uninterrupted run; async planners restore the exact sync fixed point
        the checkpoint captured, though their rebuild timing stays a race
        (plan_lag telemetry may differ, as it does between any two async
        runs). Both pinned in ``tests/test_service_resume.py``.
        """
        from repro.checkpoint import peek_meta, restore_checkpoint

        path = path or self.cfg.checkpoint_path
        if not path:
            raise ValueError(
                "no checkpoint path: pass one or set FLConfig.checkpoint_path"
            )
        # provenance first: a bundle written by a scheduler-/tracker-free
        # server must fail with WHY, not with a generic missing-leaf error
        # from the structural restore below
        _, preview = peek_meta(path)
        if self.scheduler is not None and "scheduler" not in preview:
            raise ValueError(
                "this server has a round scheduler attached but the "
                "checkpoint carries no scheduler section — it was written "
                "by a scheduler-free server"
            )
        if self.availability is not None and "availability" not in preview:
            raise ValueError(
                "this server tracks availability but the checkpoint "
                "carries no availability section — it was written by a "
                "tracker-free server"
            )
        # the scheduler subtree is variable-shaped (the harvest buffer holds
        # however many late updates the killed round produced; a fresh
        # build's reference buffer is empty) — exempt it from the shape guard
        tree, step, extra = restore_checkpoint(
            path,
            self._state_tree(),
            dynamic_prefixes=("scheduler/",) if self.scheduler is not None else (),
        )
        self.params = tree["params"]
        self._rng.bit_generator.state = extra["server_rng"]
        self.sampler.load_state(extra["sampler"], tree["sampler"])
        if self.scheduler is not None:
            self.scheduler.load_state(extra["scheduler"], tree.get("scheduler", {}))
        if self.availability is not None:
            self.availability.load_state(
                extra["availability"], tree.get("availability", {})
            )
        self.history = History.from_json(json.dumps(extra["history"]))
        self._start_round = self._round_cursor = int(step)
        return int(step)

    # -- lifecycle ----------------------------------------------------------
    # The server owns the sampler's background resources (async planner
    # workers). ``with build_experiment(spec) as srv: ...`` — or any
    # ``with FederatedServer(...)`` — guarantees they are released; before
    # this, every benchmark that built a planner="async" sampler leaked its
    # worker thread unless it remembered to call sampler.close() itself.
    def close(self) -> None:
        """Release the sampler's background resources; idempotent."""
        if not self._closed:
            self._closed = True
            self.sampler.close()

    def __enter__(self) -> "FederatedServer":
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        self.close()
