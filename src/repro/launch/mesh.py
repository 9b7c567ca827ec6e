"""Production meshes (assignment spec).

Axes:
    single pod : (data=16, model=16)              — 256 chips (TPU v5e pod)
    multi-pod  : (pod=2, data=16, model=16)       — 512 chips

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests see 1 CPU).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devs)} — the "
            "dry-run entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax (launch/dryrun.py does).")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def fsdp_axes(mesh) -> tuple:
    """The batch/FSDP axes: ('pod','data') on multipod, ('data',) otherwise."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None
