"""Main-path programs compile for one TPU v5e chip at the paper's widths.

Nothing runs: the chip is described (``v5e:2x2``), not attached, and each
program is lowered from shapes and compiled by the TPU compiler installed
here. That catches what interpret mode cannot — tiling, VMEM limits, device
memory — at no chip time. The topology is described inside a fixture, so
importing this file never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: the paper's MLP (784-50-10) and round: 100 clients x 500 samples
N_CLIENTS, N_PAD, DIM, HIDDEN, N_CLASSES = 100, 500, 784, 50, 10
D = DIM * HIDDEN + HIDDEN + HIDDEN * N_CLASSES + N_CLASSES  # 39,760
M, N_STEPS, BATCH = 10, 10, 50
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # entries compiled for a described chip cannot be read back without one
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check_fits(compiled) -> None:
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    )
    assert 0 < total < HBM_BYTES


@pytest.mark.parametrize(
    "kernel,op,n,d",
    [
        ("fused", "gram", N_CLIENTS, D),
        ("fused", "l1", N_CLIENTS, D),
        ("padded", "gram", 1000, 64),
    ],
)
def test_similarity_kernel_compiles(one_chip, kernel, op, n, d):
    from repro.kernels.similarity.kernel import pairwise_kernel, pairwise_kernel_fused

    fn = pairwise_kernel_fused if kernel == "fused" else pairwise_kernel
    G = _spec((n, d), jnp.float32, one_chip)
    compiled = fn.lower(G, op=op).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes >= n * d * 4
    _check_fits(compiled)


def test_srp_sketch_kernel_compiles(one_chip):
    from repro.kernels.sketch.kernel import srp_sketch_kernel

    X = _spec((M, D), jnp.float32, one_chip)
    compiled = srp_sketch_kernel.lower(X, d_prime=64).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= M * 64 * 4
    _check_fits(compiled)


def test_batched_round_step_compiles_for_the_paper_mlp(one_chip):
    from repro.fl.engine import batched_round_step
    from repro.models.simple import classification_loss, init_mlp
    from repro.optim import sgd

    params = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        init_mlp((DIM, HIDDEN, N_CLASSES), seed=1),
    )
    args = (
        params,
        _spec((N_CLIENTS, N_PAD, DIM), jnp.float32, one_chip),
        _spec((N_CLIENTS, N_PAD), jnp.int32, one_chip),
        _spec((M,), jnp.int32, one_chip),
        _spec((M, N_STEPS, BATCH), jnp.int32, one_chip),
        _spec((M,), jnp.float32, one_chip),
        _spec((), jnp.float32, one_chip),
    )
    compiled = batched_round_step.lower(
        *args, loss_fn=classification_loss, opt=sgd(0.05)
    ).compile()
    mem = compiled.memory_analysis()
    staged = N_CLIENTS * N_PAD * (DIM * 4 + 4)
    assert mem.argument_size_in_bytes >= staged
    # outputs: the new global model plus the (m, d) flat updates and losses
    assert mem.output_size_in_bytes >= M * D * 4
    _check_fits(compiled)
