"""Device mesh construction.

Axis conventions used across the framework:
  "data"  — data parallelism (batch dim; gradients psum here)
  "model" — tensor parallelism (attention heads / FFN width; ICI all-gathers)
  "seq"   — sequence/context parallelism (ring attention)

On a physical TPU slice jax.make_mesh picks an ICI-friendly device order.
The same code builds CPU meshes under
--xla_force_host_platform_device_count for tests and the driver's
multi-chip dry run.
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType, Mesh


def mesh_shape_for(n_devices: int, tp: int | None = None) -> dict[str, int]:
    """Default (data, model) factorization: prefer TP across the whole slice
    for serving (weights sharded, batch replicated is wrong for training but
    right for single-host inference); callers override for training."""
    tp = tp or n_devices
    if n_devices % tp:
        raise ValueError(f"tp={tp} does not divide device count {n_devices}")
    return {"data": n_devices // tp, "model": tp}


def make_mesh(
    shape: dict[str, int] | None = None,
    *,
    devices: list | None = None,
) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if shape is None:
        shape = mesh_shape_for(len(devices))
    n = 1
    for v in shape.values():
        n *= v
    if n != len(devices):
        raise ValueError(f"mesh shape {shape} != {len(devices)} devices")
    # Auto axis types = classic GSPMD: the compiler propagates shardings and
    # inserts collectives from our annotations (explicit mode would demand a
    # jax.set_mesh context at every call site — wrong trade for a framework).
    return jax.make_mesh(
        tuple(shape.values()),
        tuple(shape.keys()),
        devices=devices,
        axis_types=(AxisType.Auto,) * len(shape),
    )
