"""Production meshes (assignment spec).

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — `pod` is pure
data parallelism across the DCI; `data` doubles as the FSDP axis for
parameters; `model` is tensor/expert parallelism.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """The one place that builds a mesh: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which a
    ``pallas_call`` outside a ``shard_map`` is refused and
    ``with_sharding_constraint`` rejects logical specs; the serving and
    training paths rely on GSPMD propagation, i.e. ``Auto`` axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over locally available devices (tests / CPU examples).

    Validates the requested shape against the visible device count before
    handing off to jax, so a bad request fails with an actionable message
    instead of an opaque mesh-construction error.
    """
    if data < 1 or model < 1:
        raise ValueError(
            f"mesh axes must be positive, got data={data} model={model}")
    need = data * model
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"requested a {data}x{model} (data, model) mesh = {need} devices "
            f"but only {have} are visible; on CPU, force extra devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return make_mesh((data, model), ("data", "model"))


def mesh_from_env(var: str = "REPRO_MESH"):
    """Build a host mesh from ``REPRO_MESH=data,model`` (e.g. ``1,2``).

    Returns None when the variable is unset or empty, so call sites can do
    ``mesh = mesh_from_env()`` and fall through to unsharded serving.
    """
    import os

    spec = os.environ.get(var, "").strip()
    if not spec:
        return None
    parts = spec.replace("x", ",").split(",")
    if len(parts) != 2:
        raise ValueError(
            f"{var} must be 'data,model' (e.g. '1,2'), got {spec!r}")
    try:
        data, model = (int(p) for p in parts)
    except ValueError:
        raise ValueError(
            f"{var} must hold two integers 'data,model', got {spec!r}")
    return make_host_mesh(data, model)


def mesh_chip_count(mesh) -> int:
    import numpy as np

    return int(np.prod(list(mesh.shape.values())))
