"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state — the dry-run sets XLA_FLAGS *before* any jax initialization
and only then calls this.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis carries only data-parallel/FSDP traffic (DCN-friendly), "model" stays
inside a pod (ICI).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# ``jax.make_mesh`` defaults to Explicit axes; the sharding constraints of the
# engine, the gradient store and the model hints need Auto ones.


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over the locally available devices (tests)."""
    return jax.make_mesh((data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def resolve_fl_mesh(spec):
    """Map ``FLConfig.mesh_spec`` to a mesh (or ``None``).

    * ``None`` — no mesh: the engine's single-device behavior.
    * ``"auto"`` — every locally visible device on the "data" axis.
    * ``"DxM"`` (e.g. ``"4x1"``) or ``(D, M)`` — host mesh with D-way
      data parallelism and M-way model parallelism.
    * a ``jax.sharding.Mesh`` — used as-is.
    """
    if spec is None:
        return None
    if isinstance(spec, jax.sharding.Mesh):
        return spec
    if isinstance(spec, str):
        if spec == "auto":
            return make_host_mesh(jax.local_device_count(), 1)
        parts = spec.lower().split("x")
        if len(parts) in (1, 2) and all(p.isdigit() and p for p in parts):
            return make_host_mesh(int(parts[0]), int(parts[1]) if len(parts) == 2 else 1)
    elif isinstance(spec, (tuple, list)) and len(spec) in (1, 2):
        data, *rest = spec
        return make_host_mesh(int(data), int(rest[0]) if rest else 1)
    raise ValueError(
        f"bad mesh_spec {spec!r}; expected None, 'auto', 'DxM', (D, M), or a Mesh"
    )


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes that shard the batch / FSDP dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def data_parallel_degree(mesh) -> int:
    """Total device count across the batch axes."""
    size = 1
    for a in batch_axes(mesh):
        size *= mesh.shape[a]
    return size


def leading_batch_spec(mesh, ndim: int):
    """PartitionSpec placing an array's leading axis on the mesh's batch
    axes, trailing dims replicated — the one convention for "per-client /
    per-batch-element" arrays, shared by the FL engine's runtime constraints
    and the launch-layer lowering shardings."""
    from jax.sharding import PartitionSpec as P

    dp = batch_axes(mesh)
    lead = dp if len(dp) > 1 else dp[0]
    return P(lead, *([None] * (ndim - 1)))


def mesh_chips(mesh) -> int:
    return mesh.devices.size
