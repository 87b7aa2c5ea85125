"""Pallas kernels for the paper's device hot-spots.

Each kernel package holds ``kernel.py`` (the ``pallas_call``), ``ops.py``
(the jitted public API) and ``ref.py`` (the plain reference tests compare
against).
"""
from __future__ import annotations

import functools


def interpret_mode() -> bool:
    """Whether the main path runs its Pallas kernels in interpret mode.

    Decided by JAX's default platform: ``cpu`` (tests) interprets, ``tpu``
    compiles. Any other platform raises — the kernels use TPU-only VMEM
    scratch and Mosaic block specs, so there is no compiled path to take
    there, and running the interpreter in its place would hide that.
    """
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels compile only for 'tpu' and interpret only on "
        f"'cpu'; JAX's default platform is {platform!r}. Pin JAX_PLATFORMS "
        "to 'tpu' or 'cpu'"
    )


def call_on_mesh(kernel, x, **static):
    """``kernel(x, **static)`` for a single-device Pallas kernel, wherever
    ``x`` lives.

    XLA cannot partition a compiled Mosaic kernel, so a program that spans
    several devices and calls one does not compile. When ``x`` is spread
    over a device mesh (the sharded gradient store, the mesh engine's
    updates), every device of that mesh runs the kernel on all of ``x``
    under ``shard_map``, and the result comes back replicated over the same
    mesh, ready to meet the mesh's other arrays. Host arrays, single-device
    arrays and traced values go to ``kernel`` unchanged. ``static`` holds
    the kernel's hashable keyword options; one program is compiled per
    kernel, mesh and options.
    """
    import jax

    sharding = None if isinstance(x, jax.core.Tracer) else getattr(x, "sharding", None)
    if sharding is None or len(sharding.device_set) == 1:
        return kernel(x, **static)
    return _replicated(kernel, sharding.mesh, tuple(sorted(static.items())))(x)


@functools.lru_cache(maxsize=32)
def _replicated(kernel, mesh, static: tuple):
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.jit(
        jax.shard_map(
            functools.partial(kernel, **dict(static)),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )
    )
