"""Batched on-device FL round engine: the server's one round path.

The paper's protocol runs ``m`` sampled clients per round. A per-client loop
(:class:`repro.fl.client.ReferenceRoundEngine`, kept as the tests'
reference) costs m jitted dispatches plus m host-side copies of client data
per round, so wall-clock grows linearly in m. This engine runs the *whole
round* as ONE jitted step:

  1. every client's train set is padded to a common length and stacked once
     into a device-resident (n, n_pad, …) block at construction time;
  2. per round, the distinct sampled clients are gathered *on device* by
     slot index, and all local updates run as ``vmap(local_steps)`` — the
     same ``lax.scan`` body as the reference loop, so the two agree to fp32
     tolerance (FedProx proximal term included);
  3. the weighted aggregation (eq. 3/4 incl. ``stale_weight``) and the
     flattened representative gradients ``θ_i^{t+1} − θ^t`` (Algorithm 2
     line 1's input, fed to ``sampler.observe_updates``) are computed in the
     same jitted step — nothing round-trips through the host except the
     (m, N, B) batch-index block and the scalar losses.

The whole fleet is staged: a fleet the device cannot hold fails with the
device's own allocation error at construction.

Shapes are static across the run: the client axis is always padded to
``m_slots`` (zero weight ⇒ zero contribution for unused slots), so the
engine compiles exactly once per FL run regardless of how many *distinct*
clients each round realizes. Per-round padding waste is ``m_slots −
n_distinct`` client-updates — small, because clustered sampling exists
precisely to keep the draws distinct.

RNG discipline matches the reference loop exactly: batch indices are drawn
from the server's host rng per distinct client, in distinct order, and
padded slots consume no randomness — so the same seed yields the same
realized batches on both paths.

Tracing: a round's engine work shows in a profiler trace as three spans
inside the server's ``fl.local_work`` — ``fl.local_work.prep`` (slot ids,
batch indices, weights; counters ``distinct`` and ``slots``),
``fl.local_work.dispatch`` (the compiled step's call on the host numpy
inputs and the compiled ``(c, d)`` row slice; no eager ``jax.numpy``
operation; counter ``bytes``, what the call moves to the device) and
``fl.local_work.wait`` (the host blocking on the losses).

Mesh sharding (``mesh=`` on the engine / ``batched_round_step``): the round
is embarrassingly parallel over clients — each data-parallel group plays
one sampled client (the ``launch.fl_train`` pattern). With a mesh, the
``m_slots`` client axis (slot ids, batch indices, weights, the gathered
per-client data blocks and the vmapped per-client models) is constrained
onto the mesh's batch axes; the staged dataset is sharded over its client
axis so per-device pinned bytes shrink with mesh size; the eq. 3/4 weighted
aggregation is the single cross-client collective and the new global model
comes back replicated. ``mesh=None`` (default) places no constraints —
bit-for-bit the single-device behavior.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.registry import Registry
from repro.fl.aggregation import aggregate_stacked, flatten_params
from repro.fl.client import LossFn, local_steps
from repro.launch.mesh import data_parallel_degree, leading_batch_spec
from repro.optim.base import Optimizer


def _staged_dtypes(dataset) -> tuple[np.dtype, np.dtype]:
    """Dtypes the engine actually stages for ``dataset``.

    Floating features at or below 4 bytes keep their dtype; everything else
    (f64 — which jax would silently downcast anyway — and integer image
    bytes, which the dense matmul needs as floats) becomes f32. Integer
    labels at or below 4 bytes keep their dtype; wider ones become i32.
    """
    xd = np.dtype(dataset.clients[0].x_train.dtype)
    yd = np.dtype(dataset.clients[0].y_train.dtype)
    feat = xd if (xd.kind == "f" and xd.itemsize <= 4) else np.dtype(np.float32)
    lab = yd if (yd.kind in "iu" and yd.itemsize <= 4) else np.dtype(np.int32)
    return feat, lab


def _client_spec(mesh, ndim: int) -> NamedSharding:
    """Leading axis on the mesh's batch axes, trailing dims replicated."""
    return NamedSharding(mesh, leading_batch_spec(mesh, ndim))


@functools.partial(jax.jit, static_argnames=("loss_fn", "opt", "fedprox_mu", "mesh"))
def batched_round_step(
    global_params,
    x_all: jnp.ndarray,  # (n, n_pad, …) stacked client features
    y_all: jnp.ndarray,  # (n, n_pad) stacked client labels
    slot_ids: jnp.ndarray,  # (m_slots,) client id per slot (0 for padding)
    batch_idx: jnp.ndarray,  # (m_slots, N, B) per-slot batch indices
    weights: jnp.ndarray,  # (m_slots,) realized ω, 0 for padded slots
    stale_weight: jnp.ndarray,  # scalar, eq. 3 mass on θ^t
    *,
    loss_fn: LossFn,
    opt: Optimizer,
    fedprox_mu: float = 0.0,
    mesh=None,
):
    """One full FL round on device.

    Returns (new_global_params, (m_slots, d) flat updates, (m_slots,) mean
    local losses). Padded slots train on client 0's data with weight 0 —
    their outputs are discarded by the caller.

    ``mesh`` (a static :class:`jax.sharding.Mesh`, or ``None``) shards the
    ``m_slots`` client axis over the mesh's batch axes via sharding
    constraints: every per-slot array — and the vmapped per-client model
    copies — lives on its data-parallel group, the weighted aggregation is
    the one cross-client collective, and the aggregated model plus the
    global params stay replicated over the model axes.
    """
    if mesh is None:
        cl = lambda a: a
        repl = cl
    else:
        cl = lambda a: jax.lax.with_sharding_constraint(a, _client_spec(mesh, a.ndim))
        repl = lambda a: jax.lax.with_sharding_constraint(a, NamedSharding(mesh, P()))
    slot_ids, batch_idx, weights = cl(slot_ids), cl(batch_idx), cl(weights)
    x = cl(x_all[slot_ids])
    y = cl(y_all[slot_ids])

    def one_client(xc, yc, idxc):
        return local_steps(global_params, xc, yc, idxc, loss_fn, opt, fedprox_mu)

    client_params, losses = jax.vmap(one_client)(x, y, batch_idx)
    client_params = jax.tree_util.tree_map(cl, client_params)
    losses = cl(losses)
    new_params = aggregate_stacked(global_params, client_params, weights, stale_weight)
    new_params = jax.tree_util.tree_map(repl, new_params)
    flat_global = flatten_params(global_params)
    updates = cl(jax.vmap(lambda cp: flatten_params(cp) - flat_global)(client_params))
    return new_params, updates, losses


@functools.partial(jax.jit, static_argnums=1)
def _first_rows(updates, c: int):
    """The ``(c, d)`` rows of the realized clients, one compile per ``c``."""
    return updates[:c]


class BatchedRoundEngine:
    """Stages a :class:`~repro.data.federated.FederatedDataset` once and runs
    rounds through :func:`batched_round_step`.

    ``m_slots`` fixes the padded client axis (normally the sampler's m).
    ``mesh`` shards the staged dataset over its client axis (when the client
    count divides the mesh's data-parallel degree; replicated otherwise) and
    runs every round with the slot axis sharded — see the module docstring.
    """

    def __init__(self, dataset, m_slots: int, n_steps: int, batch_size: int, *, mesh=None):
        if m_slots <= 0:
            raise ValueError("m_slots must be positive")
        self.m_slots = int(m_slots)
        self.n_steps = int(n_steps)
        self.batch_size = int(batch_size)
        self.mesh = mesh
        self._n_train = np.array([c.n_train for c in dataset.clients])
        n_pad = int(self._n_train.max())
        feat = dataset.clients[0].x_train.shape[1:]
        feat_dt, label_dt = _staged_dtypes(dataset)
        x_all = np.zeros((dataset.n_clients, n_pad) + feat, dtype=feat_dt)
        y_all = np.zeros((dataset.n_clients, n_pad), dtype=label_dt)
        for i, c in enumerate(dataset.clients):
            x_all[i, : c.n_train] = c.x_train
            y_all[i, : c.n_train] = c.y_train
        # device-resident for the whole run; per-round traffic is indices only
        if mesh is None:
            self._x_all = jnp.asarray(x_all)
            self._y_all = jnp.asarray(y_all)
        else:
            n_dp = data_parallel_degree(mesh)
            if dataset.n_clients % n_dp == 0:
                x_sh = _client_spec(mesh, x_all.ndim)
                y_sh = _client_spec(mesh, y_all.ndim)
            else:  # uneven client count: stage replicated, still shard the round
                x_sh = NamedSharding(mesh, P())
                y_sh = NamedSharding(mesh, P())
            self._x_all = jax.device_put(x_all, x_sh)
            self._y_all = jax.device_put(y_all, y_sh)

    def per_device_staged_bytes(self) -> int:
        """Measured bytes the busiest device pins for the staged dataset
        (the per-round batch-index block is a transient, not counted)."""
        per_device: dict = {}
        for arr in (self._x_all, self._y_all):
            for shard in arr.addressable_shards:
                per_device[shard.device] = per_device.get(shard.device, 0) + shard.data.nbytes
        return max(per_device.values())

    def run_round(
        self,
        params,
        distinct: np.ndarray,
        weights: np.ndarray,
        stale_weight: float,
        rng: np.random.Generator,
        loss_fn: LossFn,
        opt: Optimizer,
        fedprox_mu: float = 0.0,
    ):
        """Returns (new_params, (c, d) flat updates, (c,) losses) for the
        ``c = len(distinct)`` realized clients."""
        c = len(distinct)
        if c == 0 or c > self.m_slots:
            raise ValueError(f"got {c} distinct clients for {self.m_slots} slots")
        with TraceAnnotation("fl.local_work.prep", distinct=c, slots=self.m_slots):
            slot_ids = np.zeros(self.m_slots, dtype=np.int32)
            slot_ids[:c] = distinct
            idx = np.zeros((self.m_slots, self.n_steps, self.batch_size), dtype=np.int32)
            for i, cid in enumerate(distinct):
                # same rng stream as the reference loop's draw_batch_indices, drawn
                # host-side (one device transfer for the whole block below)
                idx[i] = rng.integers(
                    0, int(self._n_train[int(cid)]), size=(self.n_steps, self.batch_size)
                )
            w = np.zeros(self.m_slots, dtype=np.float32)
            w[:c] = weights
            # the four 32-bit inputs the dispatch moves to the device
            h2d = slot_ids.nbytes + idx.nbytes + w.nbytes + np.dtype(np.float32).itemsize
        with TraceAnnotation("fl.local_work.dispatch", bytes=h2d):
            # host numpy inputs go straight to the compiled call, which moves
            # them to the device itself; wrapping them in jnp.asarray first
            # would add an eager dispatch each
            new_params, updates, losses = batched_round_step(
                params,
                self._x_all,
                self._y_all,
                slot_ids,
                idx,
                w,
                np.float32(stale_weight),
                loss_fn=loss_fn,
                opt=opt,
                fedprox_mu=fedprox_mu,
                mesh=self.mesh,
            )
            # updates stay a device array: the gradient store scatters them
            # back into G without a host round-trip. The (m_slots, d) -> (c, d)
            # slice is compiled, one variant per distinct-count c <= m_slots
            updates = _first_rows(updates, c)
        with TraceAnnotation("fl.local_work.wait"):
            losses = np.asarray(losses)[:c]
        return new_params, updates, losses


# --------------------------------------------------------------------------
# engine registry: FLConfig.engine resolves through this, so alternative
# round executors plug into the server (and the spec layer) by name
# --------------------------------------------------------------------------
def _batched_engine(dataset, m: int, config, mesh):
    return BatchedRoundEngine(
        dataset, m, config.n_local_steps, config.batch_size, mesh=mesh
    )


#: name -> factory(dataset, m, config, mesh) returning an engine object with
#: ``run_round(params, distinct, weights, stale_weight, rng, loss_fn, opt,
#: fedprox_mu)`` -> (new_params, (c, d) flat updates, (c,) losses).
ENGINES = Registry("engine", {"batched": _batched_engine})

register_engine = ENGINES.register
