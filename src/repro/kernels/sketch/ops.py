"""Sketch stage public API: the ``SKETCHERS`` registry + device dispatch.

The gradient store compresses every incoming representative gradient
``θ_i^{t+1} − θ^t`` from the model dimension ``d`` to a sketch dimension
``d_prime`` *before* scatter, so the resident buffer — and everything the
plan-rebuild pipeline touches downstream — scales in ``d_prime``. This
module names the available sketch constructions, mirroring
:data:`repro.core.clustering.backends.CLUSTERERS`:

    sketcher = SKETCHERS.get(name)(d_in, d_prime, seed=0)
    y = sketcher(x)            # device path when jax is present
    y = sketcher.reference(x)  # numpy host reference (jax-free)

Built-ins:

* ``"identity"`` — pass-through (``d_out == d_in``, the input object is
  returned *unchanged*, not copied or cast). This is the exact legacy
  store path: a store built with ``sketch="identity"`` is bit-for-bit the
  unsketched store, which is what the tier-1 parity gate pins.
* ``"srp"``      — signed random projection to ``d_prime`` via the
  blockwise Pallas kernel (:func:`repro.kernels.sketch.kernel.
  srp_sketch_kernel`): the (d, d_prime) Rademacher matrix is regenerated
  (block_d, d_prime) at a time from a seeded counter-based hash, never
  materialized. Inner products are preserved in expectation with JL-style
  concentration — the right default for arccos/L2 plan distances.
* ``"countsketch"`` — seeded counting sketch (one bucket + sign per input
  coordinate, O(d) state, one scatter-add); cheaper than ``srp`` per
  update, heavier-tailed distance error.

``register_sketcher("mine", factory)`` plugs a new construction into every
spec-driven experiment via ``PlannerSpec(sketch="mine")``. jax is imported
lazily — the registry, the ``identity`` sketcher and every ``reference``
path work in jax-free environments, keeping ``repro.core`` samplers
constructible there.
"""
from __future__ import annotations

from typing import Optional, Union

import numpy as np

from repro.core.registry import Registry
from repro.kernels.sketch.ref import (
    countsketch_params,
    sketch_countsketch_reference,
    sketch_srp_reference,
)

#: default d-tile of the blockwise projection (kernel and host reference
#: share it so their accumulation order — and f32 sums — line up).
SKETCH_BLOCK_D = 512


def _jax():
    try:
        import jax  # noqa: F401
    except ImportError:
        return None
    return jax


class Sketcher:
    """A fitted sketch: ``d_in`` model coordinates -> ``d_out`` sketch ones.

    Instances are cheap, stateless-on-data objects: the projection is a
    pure function of ``(name, d_in, d_out, seed)``, so a sketcher rebuilt
    from those four values (e.g. after a checkpoint restore) applies the
    *identical* compression. ``__call__`` takes the device path when jax
    is importable (device arrays in, device array out — no host copy);
    :meth:`reference` is the numpy host path the jax-free store fallback
    uses.
    """

    name = "base"

    def __init__(self, d_in: int, d_out: int, seed: int):
        self.d_in = int(d_in)
        self.d_out = int(d_out)
        self.seed = int(seed)

    def __call__(self, X):
        raise NotImplementedError

    def reference(self, X) -> np.ndarray:
        raise NotImplementedError

    def __repr__(self):
        return (
            f"{type(self).__name__}(d_in={self.d_in}, d_out={self.d_out}, "
            f"seed={self.seed})"
        )


class IdentitySketcher(Sketcher):
    """The exact legacy path: X comes back untouched (same object)."""

    name = "identity"

    def __call__(self, X):
        return X

    def reference(self, X):
        return X


class SRPSketcher(Sketcher):
    """Blockwise signed random projection (Pallas kernel on device)."""

    name = "srp"

    def __init__(self, d_in: int, d_out: int, seed: int, block_d: int = SKETCH_BLOCK_D):
        super().__init__(d_in, d_out, seed)
        self.block_d = int(block_d)

    def __call__(self, X):
        jax = _jax()
        if jax is None:
            return self.reference(X)
        import jax.numpy as jnp

        from repro.kernels import call_on_mesh, interpret_mode
        from repro.kernels.sketch.kernel import srp_sketch_kernel

        return call_on_mesh(
            srp_sketch_kernel,
            jnp.asarray(X),
            d_prime=self.d_out,
            seed=self.seed,
            block_d=self.block_d,
            interpret=interpret_mode(),
        )

    def reference(self, X) -> np.ndarray:
        return sketch_srp_reference(X, self.d_out, self.seed, block_d=self.block_d)


class CountSketcher(Sketcher):
    """Seeded counting sketch: one jitted scatter-add, O(d) hash state."""

    name = "countsketch"

    def __call__(self, X):
        jax = _jax()
        if jax is None:
            return self.reference(X)
        import jax.numpy as jnp

        bucket, sign = countsketch_params(self.d_in, self.d_out, self.seed, jnp)
        return _countsketch_apply(jnp.asarray(X), bucket, sign, self.d_out)

    def reference(self, X) -> np.ndarray:
        return sketch_countsketch_reference(X, self.d_out, self.seed)


def _countsketch_apply(X, bucket, sign, d_out: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def apply(X, bucket, sign):
        y = jnp.zeros((X.shape[0], d_out), jnp.float32)
        return y.at[:, bucket].add(X.astype(jnp.float32) * sign[None, :])

    return apply(X, bucket, sign)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
def _need_dim(name: str, d_prime: Optional[int], d_in: int) -> int:
    if d_prime is None:
        raise ValueError(
            f"sketcher {name!r} needs a sketch dimension; pass sketch_dim "
            "(PlannerSpec.sketch_dim / GradientStore(sketch_dim=...))"
        )
    d_prime = int(d_prime)
    if not 1 <= d_prime <= d_in:
        raise ValueError(
            f"sketch_dim must satisfy 1 <= d_prime <= d={d_in}, got {d_prime}"
        )
    return d_prime


def make_identity(d_in: int, d_prime: Optional[int] = None, *, seed: int = 0):
    if d_prime is not None and int(d_prime) != int(d_in):
        raise ValueError(
            f"sketch 'identity' keeps every coordinate; sketch_dim={d_prime} "
            f"!= update_dim={d_in} — drop sketch_dim or pick a compressing "
            "sketcher ('srp', 'countsketch')"
        )
    return IdentitySketcher(d_in, d_in, seed)


def make_srp(d_in: int, d_prime: Optional[int] = None, *, seed: int = 0):
    return SRPSketcher(d_in, _need_dim("srp", d_prime, d_in), seed)


def make_countsketch(d_in: int, d_prime: Optional[int] = None, *, seed: int = 0):
    return CountSketcher(d_in, _need_dim("countsketch", d_prime, d_in), seed)


#: name -> sketcher factory ``(d_in, d_prime, seed=0) -> Sketcher``.
SKETCHERS = Registry(
    "sketcher",
    {
        "identity": make_identity,
        "srp": make_srp,
        "countsketch": make_countsketch,
    },
)

register_sketcher = SKETCHERS.register


def resolve_sketcher(
    sketch: Union[str, Sketcher, None],
    d_in: int,
    d_prime: Optional[int] = None,
    *,
    seed: int = 0,
) -> Optional[Sketcher]:
    """Map a sketch argument to a fitted :class:`Sketcher` (or ``None``).

    ``None`` means *no sketch stage at all* (the store keeps the raw
    ``(n, d)`` buffer, exactly the pre-sketch code path); a string names a
    :data:`SKETCHERS` entry; an already-fitted :class:`Sketcher` passes
    through after a dimension check.
    """
    if sketch is None:
        return None
    if isinstance(sketch, Sketcher):
        if sketch.d_in != int(d_in):
            raise ValueError(
                f"sketcher expects d_in={sketch.d_in}, store has "
                f"update_dim={d_in}"
            )
        return sketch
    return SKETCHERS.get(sketch)(d_in, d_prime, seed=seed)
