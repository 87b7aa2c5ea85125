"""Jitted public API: pairwise client distances on device.

Drop-in replacement for ``repro.core.clustering.similarity.pairwise_distances``
(numpy) — Algorithm 2 passes ``distance_fn=pallas_pairwise_distances`` to run
the O(n²d) stage on TPU. On CPU the kernels run with ``interpret=True``
(:func:`repro.kernels.interpret_mode` decides for the resolved backends).

Three entry points:

* :func:`pairwise_distances_device` — one kernel launch over the full
  (n, d) block, padded to tile multiples. Right for sampler-sized ``d``.
* :func:`pairwise_distances_streamed` — the **fused** streamed path: one
  ``pallas_call`` whose grid ceil-divides the d axis, accumulating the
  Gram / L1 matrix in per-block VMEM scratch flushed into the HBM (n, n)
  output. No host chunk loop and no padded (n, d) block — G enters the
  kernel as the exact buffer it arrives as (for the planner pipeline, the
  gradient store's live device array).
* :func:`pairwise_distances_chunked` — the pre-fusion host-side d-chunk
  Python loop, kept as a parity reference and for host (numpy) G where
  transferring one (n, d_chunk) slab at a time bounds device memory.
"""
from __future__ import annotations


import jax.numpy as jnp
import numpy as np

from repro.kernels import call_on_mesh, interpret_mode
from repro.kernels.similarity.kernel import pairwise_kernel, pairwise_kernel_fused
from repro.kernels.similarity.ref import distances_from_gram

#: d above which the "auto" backend switches to the fused streamed kernel.
STREAM_D_THRESHOLD = 8192


def _l1_postprocess(d: jnp.ndarray) -> jnp.ndarray:
    d = jnp.where(jnp.eye(d.shape[0], dtype=bool), 0.0, d)
    return jnp.maximum(d, d.T)


def _check_measure(measure: str) -> str:
    if measure not in ("arccos", "l2", "l1"):
        raise ValueError(f"unknown measure {measure!r}")
    return "l1" if measure == "l1" else "gram"


def pairwise_distances_device(
    G,
    measure: str = "arccos",
    *,
    block_n: int = 128,
    block_d: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """(n, d) representative gradients -> (n, n) distance matrix."""
    G = jnp.asarray(G, jnp.float32)
    if measure in ("arccos", "l2"):
        gram = pairwise_kernel(G, op="gram", block_n=block_n, block_d=block_d, interpret=interpret)
        return distances_from_gram(gram, measure)
    if measure == "l1":
        d = pairwise_kernel(G, op="l1", block_n=block_n, block_d=block_d, interpret=interpret)
        return _l1_postprocess(d)
    raise ValueError(f"unknown measure {measure!r}")


def pairwise_distances_streamed(
    G,
    measure: str = "arccos",
    *,
    block_n: int = 128,
    block_d: int = 128,
    d_chunk: int = STREAM_D_THRESHOLD,
    interpret: bool = False,
) -> jnp.ndarray:
    """(n, d) -> (n, n) distances in **one fused kernel launch**.

    The d-streamed accumulation runs entirely inside the kernel's grid
    (:func:`~repro.kernels.similarity.kernel.pairwise_kernel_fused`): the
    (n, n) accumulator lives in HBM as the kernel output, each block
    accumulating over the d-grid in VMEM scratch, and ragged tails are
    masked in-kernel — G is never padded and no host chunk loop runs.
    ``d_chunk`` only caps the per-step tile width (``block_d``), so
    existing call sites tuned for the chunked path keep their footprint.
    Matches :func:`pairwise_distances_device` and the numpy reference to
    fp32 accumulation-order tolerance.
    """
    op = _check_measure(measure)
    n, d = G.shape
    if d == 0:
        raise ValueError("need at least one gradient coordinate")
    bd = min(block_d, max(int(d_chunk), 1))
    acc = pairwise_kernel_fused(
        jnp.asarray(G), op=op, block_n=block_n, block_d=bd, interpret=interpret
    )
    if op == "gram":
        return distances_from_gram(acc, measure)
    return _l1_postprocess(acc)


def pairwise_distances_chunked(
    G,
    measure: str = "arccos",
    *,
    block_n: int = 128,
    block_d: int = 128,
    d_chunk: int = STREAM_D_THRESHOLD,
    interpret: bool = False,
) -> jnp.ndarray:
    """(n, d) -> (n, n) distances, accumulated over host-side ``d``-chunks.

    The pre-fusion streamed path, kept as the fused kernel's parity
    reference. Both the Gram matrix and the L1 distance are sums over
    coordinates, so per-chunk kernel outputs add exactly. Host (numpy) G is
    *transferred* one chunk at a time, so the device never holds the full
    model-sized block — the right path when G does not already live on
    device.
    """
    op = _check_measure(measure)
    n, d = G.shape
    if d == 0:
        raise ValueError("need at least one gradient coordinate")
    d_chunk = max(int(d_chunk), 1)
    acc = jnp.zeros((n, n), jnp.float32)
    for lo in range(0, d, d_chunk):
        chunk = jnp.asarray(G[:, lo : lo + d_chunk], jnp.float32)
        acc = acc + pairwise_kernel(
            chunk, op=op, block_n=block_n, block_d=block_d, interpret=interpret
        )
    if op == "gram":
        return distances_from_gram(acc, measure)
    return _l1_postprocess(acc)


def make_distance_fn(
    *,
    interpret: bool = False,
    streamed: bool = False,
    d_chunk: int = STREAM_D_THRESHOLD,
    chunked: bool = False,
    as_numpy: bool = True,
):
    """Adapter matching ``repro.core.samplers.algorithm2.DistanceFn``.

    ``streamed=True`` always takes the fused streamed kernel; otherwise the
    one-shot kernel is used up to ``d_chunk`` coordinates and the fused
    kernel kicks in beyond it, so model-sized ``d`` never pays the padded
    full-width copy. ``chunked=True`` selects the legacy host-side chunk
    loop instead of the fused kernel (parity reference). ``as_numpy=False``
    returns the device array untouched — the clustering backends that run
    on device (``ward_jit``, ``kmeans``) consume it without a host copy.
    """

    def fn(G, measure: str):
        if chunked:
            kernel, kw = pairwise_distances_chunked, {"d_chunk": d_chunk}
        elif streamed or G.shape[1] > d_chunk:
            kernel, kw = pairwise_distances_streamed, {"d_chunk": d_chunk}
        else:
            kernel, kw = pairwise_distances_device, {}
        out = call_on_mesh(kernel, G, measure=measure, interpret=interpret, **kw)
        return np.asarray(out) if as_numpy else out

    return fn


def resolve_distance_backend(backend: str = "auto", *, as_numpy: bool = True):
    """Pick the pairwise-distance backend for Algorithm 2's O(n²d) stage.

    The device backends compile their Pallas kernels on TPU and run them in
    interpret mode on CPU (:func:`repro.kernels.interpret_mode`); any other
    platform is an error.

    * ``"auto"``     — the device kernel; switches to the fused streamed
      kernel once ``d`` exceeds :data:`STREAM_D_THRESHOLD`.
    * ``"pallas"``   — compiled Pallas kernel; TPU only, errors elsewhere.
    * ``"pallas-interpret"`` — interpret-mode Pallas anywhere (tests).
    * ``"streamed"`` — always the fused streamed kernel (one launch, d-grid
      in-kernel, no padded (n, d) block); for model-sized ``d``.
    * ``"chunked"``  — the legacy host-side d-chunk accumulation loop, the
      fused kernel's parity reference.
    * ``"numpy"``    — the f64 host reference
      (:func:`repro.core.clustering.similarity.pairwise_distances`).

    ``as_numpy=False`` keeps device backends' output on device (the numpy
    reference is host-side either way).
    """
    if backend == "numpy":
        from repro.core.clustering.similarity import pairwise_distances

        return pairwise_distances
    if backend == "auto":
        return make_distance_fn(interpret=interpret_mode(), as_numpy=as_numpy)
    if backend == "streamed":
        return make_distance_fn(
            interpret=interpret_mode(), streamed=True, as_numpy=as_numpy
        )
    if backend == "chunked":
        return make_distance_fn(
            interpret=interpret_mode(), chunked=True, as_numpy=as_numpy
        )
    if backend == "pallas":
        if interpret_mode():
            raise RuntimeError(
                "distance backend 'pallas' requires a TPU: it compiles its "
                "kernel with Mosaic, and JAX's default platform is 'cpu', "
                "where Pallas kernels run only in interpret mode; use 'auto' "
                "or 'pallas-interpret'"
            )
        return make_distance_fn(interpret=False, as_numpy=as_numpy)
    if backend == "pallas-interpret":
        return make_distance_fn(interpret=True, as_numpy=as_numpy)
    raise ValueError(
        f"unknown distance backend {backend!r}; "
        "choose from auto | pallas | pallas-interpret | streamed | chunked | numpy"
    )
