"""Client-side local work: N SGD steps from the received global model.

``local_steps`` is the un-jitted scan body shared by two callers:

* ``repro.fl.engine`` — the batched round engine, the server's one round
  path, vmaps ``local_steps`` over a stacked client axis so every sampled
  client's round runs in one jit.
* ``local_update`` — the jitted single-client entry point of
  :class:`ReferenceRoundEngine`, the plain per-client loop the batched
  engine is tested against; shapes are static ((N, B) index matrix), so one
  compile covers the whole FL run.

The reference is not in ``repro.fl.engine.ENGINES``: no spec or
``FLConfig`` can select it. A test (or ``chip_smoke.py``) registers
:func:`reference_engine` under a name of its own to run a server on it.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.aggregation import aggregate_round, flatten_params
from repro.optim.base import Optimizer, apply_updates

LossFn = Callable[..., jnp.ndarray]  # (params, x, y, [global_params]) -> scalar


def local_steps(
    params,
    x: jnp.ndarray,
    y: jnp.ndarray,
    batch_idx: jnp.ndarray,  # (N, B) int32 rows into x/y
    loss_fn: LossFn,
    opt: Optimizer,
    fedprox_mu: float = 0.0,
):
    """Run N local steps; returns (updated params, mean local loss)."""
    global_params = params

    def step(carry, idx):
        p, opt_state, t = carry
        xb, yb = x[idx], y[idx]
        if fedprox_mu:
            loss, grads = jax.value_and_grad(
                lambda q: loss_fn(q, xb, yb, global_params, fedprox_mu)
            )(p)
        else:
            loss, grads = jax.value_and_grad(lambda q: loss_fn(q, xb, yb))(p)
        updates, opt_state = opt.update(grads, opt_state, p, t)
        return (apply_updates(p, updates), opt_state, t + 1), loss

    init = (params, opt.init(params), jnp.zeros((), jnp.int32))
    (new_params, _, _), losses = jax.lax.scan(step, init, batch_idx)
    return new_params, losses.mean()


@functools.partial(jax.jit, static_argnames=("loss_fn", "opt", "fedprox_mu"))
def local_update(
    params,
    x: jnp.ndarray,
    y: jnp.ndarray,
    batch_idx: jnp.ndarray,
    loss_fn: LossFn,
    opt: Optimizer,
    fedprox_mu: float = 0.0,
):
    """Jitted single-client round (the reference engine's step)."""
    return local_steps(params, x, y, batch_idx, loss_fn, opt, fedprox_mu)


def draw_batch_indices(rng, n_data: int, n_steps: int, batch_size: int) -> jnp.ndarray:
    """Pre-draw the (N, B) batch index matrix for one client round."""
    return jnp.asarray(
        rng.integers(0, n_data, size=(n_steps, batch_size)), dtype=jnp.int32
    )


class ReferenceRoundEngine:
    """The plain per-client loop: one jitted dispatch per distinct client.

    Same contract as :meth:`repro.fl.engine.BatchedRoundEngine.run_round`
    and the same rng stream (batch indices drawn per distinct client, in
    distinct order), so a server on either engine realizes the same rounds
    and agrees to fp32 tolerance. It copies each client's data to the
    default device every round and never shards: a numerics reference, not
    a served path.
    """

    def __init__(self, dataset, n_steps: int, batch_size: int):
        self.dataset = dataset
        self.n_steps = int(n_steps)
        self.batch_size = int(batch_size)

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
        client_models, losses, updates_flat = [], [], []
        flat_global = flatten_params(params)
        for cid in distinct:
            data = self.dataset.clients[int(cid)]
            idx = draw_batch_indices(rng, data.n_train, self.n_steps, self.batch_size)
            new_p, loss = local_update(
                params,
                jnp.asarray(data.x_train),
                jnp.asarray(data.y_train),
                idx,
                loss_fn,
                opt,
                fedprox_mu,
            )
            client_models.append(new_p)
            losses.append(float(loss))
            updates_flat.append(np.asarray(flatten_params(new_p) - flat_global))
        new_params = aggregate_round(params, client_models, weights, stale_weight)
        return new_params, np.stack(updates_flat), np.asarray(losses)


def reference_engine(dataset, m: int, config, mesh) -> ReferenceRoundEngine:
    """An ``ENGINES``-shaped factory for :class:`ReferenceRoundEngine`; the
    slot count and the mesh do not apply to a per-client loop."""
    del m, mesh
    return ReferenceRoundEngine(dataset, config.n_local_steps, config.batch_size)
