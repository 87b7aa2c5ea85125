"""The MLP model kind: dense layers with ReLU between them, sized by the
configuration (``data.dim``, ``train.hidden``, ``data.n_classes``).

The program's side is ``build_experiment``'s own MLP (``train.hidden`` and
``n_classes`` in the spec), its weights replaced by the benchmark's; the
reference's side (:func:`local_train`, :func:`evaluate`) is written here,
apart from the program: leaves ``w0, b0, w1, ...`` as the program names
them, softmax cross-entropy, plain SGD.
"""
from __future__ import annotations

import functools

import numpy as np

import harness
import inputs
from counts import mlp_param_count, round_train_flops


def mlp_dims(cfg: dict) -> tuple[int, ...]:
    """(in, hidden..., out) of the configuration's MLP."""
    return (int(cfg["data"]["dim"]), *map(int, cfg["train"]["hidden"]),
            int(cfg["data"]["n_classes"]))


def init_params(dims: tuple[int, ...], seed: int) -> dict:
    """He-normal MLP weights and zero biases, made on the device in one jitted call.

    Leaves are named as the program's MLP names them (``w0``, ``b0``, ...).
    """
    import jax
    import jax.numpy as jnp

    def init(key):
        keys = jax.random.split(key, len(dims) - 1)
        out = {}
        for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
            w = jax.random.normal(keys[i], (d_in, d_out), jnp.float32)
            out[f"w{i}"] = w * jnp.float32(np.sqrt(2.0 / d_in))
            out[f"b{i}"] = jnp.zeros((d_out,), jnp.float32)
        return out

    return jax.jit(init)(jax.random.key(seed))


def build(cfg: dict, mix: dict, seeds: dict):
    """(server, the reference's inputs): the clients' data and the weights
    from the seeds, the server with the program's MLP of the same widths."""
    from repro.data.federated import ClientData, FederatedDataset
    from repro.fl.experiment import build_experiment

    clients = inputs.make_clients(cfg["data"], seeds["data"])
    dataset = FederatedDataset([ClientData(*c) for c in clients])
    params0 = init_params(mlp_dims(cfg), seeds["model"])
    spec = harness.experiment_dict(cfg, mix, seeds)
    spec["train"].update(hidden=cfg["train"]["hidden"], n_classes=cfg["data"]["n_classes"])
    srv = build_experiment(spec, dataset=dataset)
    srv.params = params0
    return srv, harness.reference_inputs(cfg, mix, seeds, clients, params0)


def _mlp(params, x, n_layers):
    import jax

    h = x
    for i in range(n_layers):
        h = h @ params[f"w{i}"] + params[f"b{i}"]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h


@functools.lru_cache(maxsize=None)
def local_train(dtype_name: str):
    """One client's ``N`` SGD steps in ``dtype_name``: ``(params, x, y, (N, B)
    rows, lr) -> (float32 params, mean step loss)``."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def loss_fn(p, xb, yb):
        logits = _mlp(p, xb, len(p) // 2).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1).mean()

    def run(params, x, y, idx, lr):
        p = {k: v.astype(dtype) for k, v in params.items()}
        x = x.astype(dtype)

        def step(p, rows):
            loss, g = jax.value_and_grad(loss_fn)(p, x[rows], y[rows])
            return {k: (p[k] - lr.astype(dtype) * g[k].astype(dtype)).astype(dtype)
                    for k in p}, loss

        p, losses = jax.lax.scan(step, p, idx)
        return {k: v.astype(jnp.float32) for k, v in p.items()}, losses.mean()

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def evaluate(dtype_name: str):
    """``(params, x_test, y_test) -> accuracy`` in ``dtype_name``."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def acc(params, x, y):
        p = {k: v.astype(dtype) for k, v in params.items()}
        return (_mlp(p, x.astype(dtype), len(p) // 2).argmax(-1) == y).mean(dtype=jnp.float32)

    return jax.jit(acc)


def shapes(cfg: dict, srv) -> dict:
    """``n_params`` and ``train_flops_per_client``, 6 P per sample over N x B samples."""
    n_params = mlp_param_count(mlp_dims(cfg))
    tr = cfg["train"]
    return {"n_params": n_params,
            "train_flops_per_client": round_train_flops(n_params, 1, tr["n_local_steps"],
                                                        tr["batch_size"])}
