"""Pallas TPU kernel for the blockwise signed-random-projection sketch.

y = X @ S with X (n, d) and S a (d, d_prime) scaled Rademacher matrix that
is **never materialized**: each grid step regenerates its (block_d,
d_prime) slice of S inside the kernel from the counter-based hash shared
with the host reference (:mod:`repro.kernels.sketch.ref`), multiplies it
against the matching (block_n, block_d) X tile on the MXU, and accumulates
into a (block_n, d_prime) VMEM scratch that flushes to the HBM output on
the last d-step. Structure mirrors
:func:`repro.kernels.similarity.kernel.pairwise_kernel_fused`:

* grid (⌈n/bn⌉, ⌈d/bd⌉), d innermost; X is consumed as the exact HBM
  buffer it arrives as — no padded (n, d) copy ever exists;
* the ragged d-tail is masked *inside* the sign generation (rows of S at
  or beyond d are zero, exact for the matmul); ragged n-tail rows land in
  the padded output buffer and are sliced away by the caller;
* VMEM footprint per step: bn·bd X tile + bd·d_prime sign tile + bn·d_prime
  accumulator — ~(128·512 + 512·64 + 128·64)·4 B ≈ 420 KiB at defaults.

``interpret=True`` runs the identical program as jax ops on CPU (the
same convention as the similarity kernels; :func:`repro.kernels.
interpret_mode` picks it from the platform).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sketch.ref import srp_sign_entries


def _srp_kernel(seed: int, d: int, d_prime: int, bn: int, bd: int):
    def kernel(x_ref, o_ref, acc):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        # regenerate this step's (bd, d_prime) slice of the projection from
        # the hash — global coordinate k = d-step * bd + local row
        k = (
            jax.lax.broadcasted_iota(jnp.uint32, (bd, d_prime), 0)
            + jnp.uint32(pl.program_id(1) * bd)
        )
        j = jax.lax.broadcasted_iota(jnp.uint32, (bd, d_prime), 1)
        signs = srp_sign_entries(k, j, seed, d, d_prime, jnp)
        # d-tail columns of the X tile hit zeroed sign rows, so OOB lanes
        # of the *input* read must be zeroed too (garbage · 0 is still
        # defined, but garbage may be NaN — mask it away)
        col = jax.lax.broadcasted_iota(jnp.int32, (bn, bd), 1) + pl.program_id(1) * bd
        x = jnp.where(col < d, x_ref[...], 0.0)
        acc[...] += jax.lax.dot_general(
            x, signs, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
        def _flush():
            o_ref[...] = acc[...]

    return kernel


@functools.partial(
    jax.jit, static_argnames=("d_prime", "seed", "block_n", "block_d", "interpret")
)
def srp_sketch_kernel(
    X: jnp.ndarray,
    *,
    d_prime: int,
    seed: int = 0,
    block_n: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """X (n, d) f32 -> (n, d_prime) sketch, one launch, no padded X copy.

    Matches :func:`repro.kernels.sketch.ref.sketch_srp_reference` to f32
    accumulation-order tolerance (same hash, same blockwise ordering when
    ``block_d`` agrees).
    """
    X = X.astype(jnp.float32)
    n, d = X.shape
    dp = int(d_prime)
    if dp < 1:
        raise ValueError(f"d_prime must be >= 1, got {d_prime}")
    bn = min(block_n, max(8, n))
    bd = min(block_d, max(8, d))
    gn = -(-n // bn)
    gd = -(-d // bd)
    out = pl.pallas_call(
        _srp_kernel(int(seed), d, dp, bn, bd),
        grid=(gn, gd),
        in_specs=[pl.BlockSpec((bn, bd), lambda i, k: (i, k))],
        out_specs=pl.BlockSpec((bn, dp), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((gn * bn, dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, dp), jnp.float32)],
        interpret=interpret,
    )(X)
    return out[:n]
