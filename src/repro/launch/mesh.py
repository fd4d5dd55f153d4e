"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state.  Single pod: (data=16, model=16)
= 256 chips (TPU v5e pod slice); multi-pod: (pod=2, data=16, model=16) =
512 chips, with the ``pod`` axis carrying pure data parallelism.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.  Since JAX 0.7 the default
    axis type is ``Explicit``, under which the sharding hints and the
    expert-parallel output reshapes this repo relies on are rejected;
    every mesh here is built through this helper instead."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def ep_mesh(n_experts: int, devices=None):
    """(data=1, model=n) expert-parallel mesh over ``devices`` (default:
    every device JAX sees — the chips of one TPU host, or host-platform
    CPU devices forced from outside).  Raises unless the experts divide
    evenly over at least two devices."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if n < 2 or n_experts % n:
        raise ValueError(
            f"expert parallelism needs >= 2 devices dividing "
            f"{n_experts} experts; JAX sees {n} "
            f"({devices[0].platform}) — on a CPU host set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8")
    return make_mesh((1, n), ("data", "model"), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """Mesh axes that carry the batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh, *names) -> int:
    n = 1
    for a in names:
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
