"""Activation-sharding hints for GSPMD, usable from pure model code.

GSPMD does not propagate tensor-parallel sharding through the GQA reshape
chain (verified in the dry-run: per-chip HLO carried all heads — attention
replicated 16× across the model axis). Constraints are therefore placed on
the big attention/MoE intermediates directly.

Design: head counts in the zoo (40, 24, 16, 12, 1 kv …) don't uniformly
divide the model axis, so the portable scheme is *sequence-parallel*
attention — scores are sharded over the query-sequence dim for full passes
and over the key/cache dim for single-token decode. Both divide 16 for
every assigned shape (4096, 32768, window 8192, 524288).

Model code calls ``constrain(x, "dp", None, "model", ...)``; the tokens
"dp" / "model" are resolved against the active hint set by the launch layer
(``with sharding_hints(...)``). Without hints (unit tests, FL tier) every
call is a no-op, keeping the model code mesh-agnostic.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def _current():
    return getattr(_state, "hints", None)


@contextlib.contextmanager
def sharding_hints(dp_axes, model_axis: str = "model"):
    """dp_axes: axis name or tuple ('pod','data') sharding batch/seq-ish dims."""
    prev = _current()
    _state.hints = (dp_axes, model_axis)
    try:
        yield
    finally:
        _state.hints = prev


def constrain(x, *dims):
    """dims entries: 'dp' | 'model' | None. No-op when no hints are active
    or a dimension does not divide the axis size."""
    hints = _current()
    if hints is None:
        return x
    dp, model = hints
    mesh = _active_mesh_shape()
    spec = []
    for d, size in zip(dims, x.shape):
        if d == "dp":
            n = _axes_size(mesh, dp)
            spec.append(dp if n and size % n == 0 and size >= n else None)
        elif d == "model":
            n = _axes_size(mesh, model)
            spec.append(model if n and size % n == 0 and size >= n else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _active_mesh_shape():
    env = jax.sharding.get_abstract_mesh()
    if env.axis_names:
        return dict(zip(env.axis_names, env.axis_sizes))
    # fall back to the physical mesh context
    try:
        from jax.interpreters.pxla import thread_resources

        m = thread_resources.env.physical_mesh
        if m is not None and m.axis_names:
            return dict(m.shape)
    except Exception:  # noqa: BLE001
        pass
    return None


def _axes_size(mesh_shape, axes):
    if mesh_shape is None:
        return None
    if isinstance(axes, str):
        return mesh_shape.get(axes)
    n = 1
    for a in axes:
        if a not in mesh_shape:
            return None
        n *= mesh_shape[a]
    return n
