"""A cell's inputs, made from its seed: the seeds of each part and every client's data.

The benchmark makes these itself, so the plain reference (``reference.py``)
reads the same arrays without taking anything the program made; the initial
weights are the model kind's (``models/<kind>.py``), from the ``model``
seed. One ``--seed`` gives one set of inputs; the sizes come from the
configuration alone, so every seed does the same amount of work.

Features are class-conditional Gaussians (``x ~ N(mu_y, noise^2 I)`` with
``mu_c ~ N(0, class_scale^2 I)``), drawn in one float32 block for the whole
fleet. Two partitions, after the paper's two settings:

* ``by_class_shards``: client ``c`` holds only class ``c // clients_per_class``;
  every client has ``train_per_client`` / ``test_per_client`` samples;
* ``dirichlet_labels``: client ``c``'s labels follow a mixture drawn from
  ``Dir(alpha)``; client sizes follow ``size_profile`` (``[count, n_train]``
  pairs), with ``test_fraction`` of that as test samples.
"""
from __future__ import annotations

import numpy as np


def derive_seeds(seed: int) -> dict:
    """Independent 32-bit seeds for the data, the sampler, the server and the model."""
    data, sampler, train, model = np.random.SeedSequence(int(seed)).generate_state(4)
    return {"data": int(data), "sampler": int(sampler), "train": int(train),
            "model": int(model)}


def client_sizes(data: dict) -> tuple[list[int], list[int]]:
    """(train sizes, test sizes) per client."""
    part = data["partition"]
    if part == "by_class_shards":
        n = data["n_classes"] * data["clients_per_class"]
        return [data["train_per_client"]] * n, [data["test_per_client"]] * n
    if part == "dirichlet_labels":
        train = [int(size) for count, size in data["size_profile"] for _ in range(count)]
        return train, [max(int(size * data["test_fraction"]), 1) for size in train]
    raise ValueError(f"unknown partition {part!r}")


def client_labels(data: dict, rng: np.random.Generator) -> list[np.ndarray]:
    """Each client's labels, train samples then test samples, as int32."""
    train, test = client_sizes(data)
    n_classes = data["n_classes"]
    if data["partition"] == "by_class_shards":
        per = data["clients_per_class"]
        return [np.full(a + b, c // per, np.int32) for c, (a, b) in enumerate(zip(train, test))]
    mixtures = rng.dirichlet(np.full(n_classes, float(data["alpha"])), size=len(train))
    return [
        rng.choice(n_classes, size=a + b, p=mix / mix.sum()).astype(np.int32)
        for a, b, mix in zip(train, test, mixtures)
    ]


def make_clients(data: dict, seed: int) -> list[tuple[np.ndarray, ...]]:
    """``[(x_train, y_train, x_test, y_test), ...]`` per client, float32 / int32.

    The arrays of one client are views into one fleet-wide block.
    """
    rng = np.random.default_rng(seed)
    dim, n_classes = int(data["dim"]), int(data["n_classes"])
    means = rng.standard_normal((n_classes, dim), dtype=np.float32)
    means *= np.float32(data["class_scale"])
    labels = client_labels(data, rng)
    y = np.concatenate(labels)
    x = rng.standard_normal((y.size, dim), dtype=np.float32)
    x *= np.float32(data["noise"])
    train, _ = client_sizes(data)
    clients, start = [], 0
    for n_train, lab in zip(train, labels):
        stop = start + lab.size
        block = x[start:stop]
        block += means[lab]
        clients.append((block[:n_train], lab[:n_train], block[n_train:], lab[n_train:]))
        start = stop
    return clients
