"""A second model kind for the tests, copied into a tree as ``models/toy.py``.

A one-hidden-layer tanh network whose weights are a nested pytree,
``{"body": [{"w", "b"}], "head": {"w", "b"}}``, on the fleet's classification
data. The program runs it through ``build_experiment`` with the loss and the
accuracy below; the reference's half (:func:`local_train`, :func:`evaluate`)
is written apart from them. Its check covers three rounds, by its own
``check_round_count``.
"""
from __future__ import annotations

import functools

import harness
import inputs


def _dims(cfg):
    return int(cfg["data"]["dim"]), int(cfg["model"]["width"]), int(cfg["data"]["n_classes"])


def init_params(cfg, seed):
    import jax
    import jax.numpy as jnp

    d, h, k = _dims(cfg)

    def init(key):
        k1, k2 = jax.random.split(key)
        return {"body": [{"w": jax.random.normal(k1, (d, h), jnp.float32) / jnp.sqrt(d),
                          "b": jnp.zeros((h,), jnp.float32)}],
                "head": {"w": jax.random.normal(k2, (h, k), jnp.float32) / jnp.sqrt(h),
                         "b": jnp.zeros((k,), jnp.float32)}}

    return jax.jit(init)(jax.random.key(seed))


def _logits(params, x):
    import jax.numpy as jnp

    (layer,) = params["body"]
    return jnp.tanh(x @ layer["w"] + layer["b"]) @ params["head"]["w"] + params["head"]["b"]


def loss(params, x, y):
    import jax

    logp = jax.nn.log_softmax(_logits(params, x), axis=-1)
    return -(logp * jax.nn.one_hot(y, logp.shape[-1])).sum(-1).mean()


def accuracy(params, x, y):
    return (_logits(params, x).argmax(-1) == y).mean()


def build(cfg, mix, seeds):
    from repro.data.federated import ClientData, FederatedDataset
    from repro.fl.experiment import build_experiment

    clients = inputs.make_clients(cfg["data"], seeds["data"])
    dataset = FederatedDataset([ClientData(*c) for c in clients])
    params0 = init_params(cfg, seeds["model"])
    srv = build_experiment(harness.experiment_dict(cfg, mix, seeds), dataset=dataset,
                           loss_fn=loss, acc_fn=accuracy)
    srv.params = params0
    return srv, harness.reference_inputs(cfg, mix, seeds, clients, params0)


def _forward(p, x):
    import jax.numpy as jnp

    hidden = jnp.tanh(jnp.dot(x, p["body"][0]["w"]) + p["body"][0]["b"])
    return jnp.dot(hidden, p["head"]["w"]) + p["head"]["b"]


@functools.lru_cache(maxsize=None)
def local_train(dtype_name):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def xent(p, xb, yb):
        logp = jax.nn.log_softmax(_forward(p, xb).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1).mean()

    def run(params, x, y, idx, lr):
        cast = lambda t, dt: jax.tree_util.tree_map(lambda v: v.astype(dt), t)
        x = x.astype(dtype)

        def step(p, rows):
            value, g = jax.value_and_grad(xent)(p, x[rows], y[rows])
            return jax.tree_util.tree_map(
                lambda a, b: (a - lr.astype(dtype) * b.astype(dtype)).astype(dtype), p, g), value

        p, losses = jax.lax.scan(step, cast(params, dtype), idx)
        return cast(p, jnp.float32), losses.mean()

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def evaluate(dtype_name):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def acc(params, x, y):
        p = jax.tree_util.tree_map(lambda v: v.astype(dtype), params)
        return (_forward(p, x.astype(dtype)).argmax(-1) == y).mean(dtype=jnp.float32)

    return jax.jit(acc)


def shapes(cfg, srv):
    d, h, k = _dims(cfg)
    n_params = d * h + h + h * k + k
    tr = cfg["train"]
    return {"n_params": n_params,
            "train_flops_per_client": 6 * n_params * tr["n_local_steps"] * tr["batch_size"]}


def check_round_count(cfg, n_clients, m):
    return 3
