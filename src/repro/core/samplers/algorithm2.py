"""Algorithm 2 — clustered sampling based on model similarity (Section 5).

Pipeline per re-clustering round:
  1. similarity matrix over representative gradients ``G_i = θ_i - θ``
     (device-side, Pallas kernel on TPU; numpy here),
  2. Ward hierarchical clustering,
  3. cut into K >= m groups with mass q_k <= M,
  4. cluster-seeded urn filling -> ``r`` matrix.

Clients never sampled yet carry a constant 0 representative gradient, so
they cluster together and get promoted jointly (the paper's cold-start
rule). Clients with ``p_i >= 1/m`` receive ``floor(m p_i)`` dedicated
probability-1 distributions, their remainder mass joining the common pool
(final remark of Section 5).

The sampler is the *consumer* half of a producer/consumer split: gradients
live in a device-resident :class:`repro.fl.gradient_store.GradientStore`
(scatter-updated from the engine's round output, no host round-trip) and
plan rebuilds run through a :class:`repro.fl.planner.PlanService` —
synchronous by default, or overlapped with client local work via
``planner="async"`` (the paper's Section-5 overlap made explicit).
"""
from __future__ import annotations

from typing import Callable, Optional, Union

import numpy as np

from repro.core.allocation import allocate_by_groups
from repro.core.clustering.backends import resolve_clusterer
from repro.core.samplers.store_backed import StoreBackedSampler
from repro.core.types import ClientPopulation, SamplingPlan

# pairwise-distance backend signature: (G, measure) -> (n, n) distances
DistanceFn = Callable[[np.ndarray, str], np.ndarray]

# clusterer signature: see repro.core.clustering.backends
ClustererFn = Callable[..., list]


def _resolve_distance_fn(
    distance_fn: Union[DistanceFn, str, None], *, as_numpy: bool = False
) -> Optional[DistanceFn]:
    """Map the sampler's ``distance_fn`` argument to a callable.

    Strings name a backend (see
    :func:`repro.kernels.similarity.ops.resolve_distance_backend`); the
    import is deferred so ``repro.core`` stays importable without jax.
    ``None`` keeps the numpy host reference. ``as_numpy=False`` leaves
    device backends' (n, n) output on device — the clustering backend
    decides whether it ever visits host (the numpy Ward reference copies
    it; ``ward_jit``/``kmeans`` never do).
    """
    if distance_fn is None or callable(distance_fn):
        return distance_fn
    from repro.kernels.similarity.ops import resolve_distance_backend

    return resolve_distance_backend(distance_fn, as_numpy=as_numpy)


def _fit_chunks(ids: np.ndarray, mass: np.ndarray, capacity: int) -> list[np.ndarray]:
    """Greedy first-fit packing of ``ids`` into chunks of mass <= capacity.

    Every pool client's remainder mass is < M by construction (it is
    ``m·n_i mod M``), so each singleton fits and the greedy pass always
    succeeds; the chunk count is at most twice the optimum, which only
    costs a few extra (still feasible) groups.
    """
    chunks: list[np.ndarray] = []
    cur: list[int] = []
    cur_mass = 0
    for i in ids:
        mi = int(mass[i])
        if cur and cur_mass + mi > capacity:
            chunks.append(np.asarray(cur, dtype=np.int64))
            cur, cur_mass = [], 0
        cur.append(int(i))
        cur_mass += mi
    if cur:
        chunks.append(np.asarray(cur, dtype=np.int64))
    return chunks


def build_plan_algorithm2(
    population: ClientPopulation,
    m: int,
    G,
    *,
    measure: str = "arccos",
    distance_fn: Optional[DistanceFn] = None,
    clusterer: Union[ClustererFn, str] = "ward",
    clusterer_seed: int = 0,
    cluster_mask: Optional[np.ndarray] = None,
) -> SamplingPlan:
    """Build the similarity-clustered ``r`` matrix for one round.

    ``G`` is passed to the clustering backend untouched — a device array
    stays on device through the O(n²d) distance stage and (for the device
    clusterers) the clustering itself; only the group structure comes back
    to host for the final urn construction. ``clusterer`` names a
    :data:`repro.core.clustering.backends.CLUSTERERS` entry (``"ward"`` —
    the paper-faithful numpy reference and default; ``"ward_jit"``;
    ``"kmeans"``) or is a callable with the same signature.

    ``cluster_mask`` ((n,) bool, optional) restricts the expensive
    *similarity clustering* to the masked-in clients — the FedSTaS-style
    restratification over the recently-available fleet. Masked-out pool
    clients keep their exact eq. (8) token mass but are packed into greedy
    capacity-feasible filler groups (``cluster_of = -1``) instead of riding
    the O(n²d + n³) pipeline. Because every group still carries <= M tokens
    over the same ``m_pool·M`` total, the allocation stays a valid eq. (8)
    plan — Proposition 1 exactness and ``conditional_plan`` unbiasedness
    over any availability mask hold regardless of the mask that built it.
    A degenerate mask (all-in, or excluding every pool client) falls back
    to the unrestricted build.
    """
    n = population.n_clients
    M = population.total_samples
    mass = m * population.n_samples  # m * n_i tokens per client

    # --- large clients: dedicated probability-1 urns --------------------
    full_urns = (mass // M).astype(np.int64)  # floor(m p_i) per client
    pool_mass = mass - full_urns * M  # remainder joins the pool
    m_pool = m - int(full_urns.sum())
    if m_pool < 0:
        raise ValueError("impossible: sum floor(m p_i) > m")

    tokens = np.zeros((m, n), dtype=np.int64)
    owners = np.repeat(np.arange(n), full_urns)  # urn k -> its dedicated client
    tokens[np.arange(owners.size), owners] = M
    urn = int(owners.size)

    cluster_of = np.full(n, -1, dtype=np.int64)
    if m_pool > 0:
        pool = np.flatnonzero(pool_mass > 0)
        mask = None
        if cluster_mask is not None:
            cm = np.asarray(cluster_mask, dtype=bool)
            if cm.shape != (n,):
                raise ValueError(f"cluster_mask shape {cm.shape} != ({n},)")
            if not cm.all() and cm[pool].any():
                mask = cm
        cluster = resolve_clusterer(clusterer)
        if mask is None:
            clustered, chunks = pool, []
            m_target = m_pool
        else:
            clustered = pool[mask[pool]]
            chunks = _fit_chunks(pool[~mask[pool]], pool_mass, M)
            # the clusterer needs >= 1 target group and cannot cut more
            # groups than it has clients; feasibility of the combined
            # grouping is automatic (every group <= M over m_pool·M total
            # mass forces K >= m_pool)
            m_target = max(1, min(clustered.size, m_pool - len(chunks)))
        groups_local = cluster(
            G[clustered],
            pool_mass[clustered],
            m_target,
            M,
            measure=measure,
            distance_fn=distance_fn,
            seed=clusterer_seed,
        )
        groups = [clustered[g] for g in groups_local]
        for gid, g in enumerate(groups):
            cluster_of[g] = gid  # filler chunks stay -1: not similarity groups
        pool_tokens = allocate_by_groups(pool_mass, m_pool, M, groups + chunks)
        tokens[urn:, :] = pool_tokens

    return SamplingPlan(r=tokens / M, r_tokens=tokens, cluster_of=cluster_of)


class Algorithm2Sampler(StoreBackedSampler):
    """Similarity-based clustered sampling with online re-clustering.

    The latest representative gradient of every client (zeros until first
    sampled) lives in a device-resident gradient store; observing a round's
    updates scatters them in and hands a snapshot to the plan service, which
    rebuilds the plan — inline (``planner="sync"``) or on a background
    worker overlapping the next round (``planner="async"``), matching the
    paper's server that overlaps re-clustering with client local work. The
    freshest completed plan is swapped in at each round boundary (in
    :meth:`sample`). The store/service machinery is the shared
    :class:`~repro.core.samplers.store_backed.StoreBackedSampler` skeleton;
    this class contributes only the Section-5 plan construction.
    """

    scheme_name = "algorithm2"

    def __init__(
        self,
        population: ClientPopulation,
        m: int,
        update_dim: int,
        *,
        measure: str = "arccos",
        seed: int = 0,
        distance_fn: Union[DistanceFn, str, None] = "auto",
        clusterer: Union[ClustererFn, str] = "ward",
        staleness_decay: float = 1.0,
        planner: str = "sync",
        rebuild_every: int = 1,
        drift_threshold: Optional[float] = None,
        sketch: Optional[str] = None,
        sketch_dim: Optional[int] = None,
        store_mesh_spec=None,
    ):
        """``staleness_decay`` < 1 is a beyond-paper extension: every round,
        stored representative gradients shrink by this factor, so clients
        that have not been sampled for many rounds drift back toward the
        zero-vector (cold-start) cluster instead of being clustered on
        arbitrarily stale similarity. 1.0 = the paper's behaviour.

        ``distance_fn`` selects the O(n²d) pairwise-distance backend: a
        backend name (``"auto"`` — the default device path: compiled Pallas
        on TPU, interpret-mode Pallas on CPU, an error on any other
        platform; ``"pallas"`` — TPU only, errors elsewhere;
        ``"pallas-interpret"``; ``"streamed"`` — d-chunked
        accumulation for model-sized gradients; ``"numpy"``), a custom
        callable, or ``None`` for the numpy host reference.

        ``clusterer`` selects the grouping backend for the pool clients
        (a ``CLUSTERERS`` name — ``"ward"`` default, ``"ward_jit"``,
        ``"kmeans"`` — or a callable; see
        :mod:`repro.core.clustering.backends`). The device clusterers
        consume the distance matrix / G where the store left them, so the
        rebuild never materializes a host copy of the gradient block.

        ``planner`` selects when Algorithm 2's O(n²d + n³) rebuild runs:
        ``"sync"`` inside ``observe_updates`` (the parity reference) or
        ``"async"`` on a background worker while the next round trains.
        ``rebuild_every=k`` re-clusters only every k observed rounds — the
        gradient store still absorbs every round's updates, so the k-th
        rebuild sees all of them (``RoundRecord.plan_version`` records which
        observation each round's plan incorporates). ``drift_threshold``
        replaces the fixed cadence with the planner's measured trigger: a
        rebuild runs only when the assignment churn of the fresh gradients
        against the live plan's clusters reaches the threshold (see
        :class:`repro.fl.planner.AssignmentDriftMonitor`).

        ``sketch`` / ``sketch_dim`` attach a device-side sketch stage to the
        gradient store (a :data:`repro.kernels.sketch.SKETCHERS` name —
        ``"srp"``, ``"countsketch"``, or ``"identity"`` for the exact
        bit-for-bit legacy path): the engine's (c, d) device updates are
        compressed to (c, d') *before* scatter, so the resident store, the
        O(n²·d) similarity stage and the drift monitor's centroids all live
        in sketch space. The sketch is seeded with the sampler ``seed``, so
        a checkpointed store restores against the identical projection.
        ``store_mesh_spec`` shards the store's client axis over a device
        mesh (the PR 2 engine mesh convention)."""
        self.measure = measure
        self._distance_fn = _resolve_distance_fn(distance_fn)
        self._clusterer = clusterer
        self._clusterer_seed = int(seed)
        super().__init__(
            population,
            m,
            update_dim,
            seed=seed,
            staleness_decay=staleness_decay,
            planner=planner,
            rebuild_every=rebuild_every,
            drift_threshold=drift_threshold,
            sketch=sketch,
            sketch_dim=sketch_dim,
            store_mesh_spec=store_mesh_spec,
        )

    def _build_plan(self, G) -> SamplingPlan:
        return build_plan_algorithm2(
            self.population,
            self.m,
            G,
            measure=self.measure,
            distance_fn=self._distance_fn,
            clusterer=self._clusterer,
            clusterer_seed=self._clusterer_seed,
            # None unless an AvailabilityTracker is attached; read at build
            # time (tracker buffers are replaced, never mutated, so the
            # async worker sees a consistent mask)
            cluster_mask=self._cluster_mask(),
        )
