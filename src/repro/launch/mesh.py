"""Production meshes (TPU v5e pods).

Defined as FUNCTIONS so importing this module never touches jax device
state — jax locks the device count on first backend initialization, and the
dry-run must set XLA_FLAGS before that happens.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e per-chip constants (used by repro.roofline)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW = 50e9                  # bytes/s per link


def make_mesh(shape, axes, *, devices=None):
    """Every mesh of the repo is built here, with ``Auto`` axes.

    The code states its shardings with ``NamedSharding``/``shard_map``
    specs and lets GSPMD propagate the rest. ``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which sharding-in-types refuses, e.g., a
    ``jnp.take`` from the vocab-sharded embedding table and asks every
    jit for a ``jax.set_mesh`` context.
    """
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for subprocess tests (device count forced by XLA_FLAGS)."""
    return make_mesh((data, model), ("data", "model"))


def make_train_mesh(data: int = 1, model: int = 1, pipe: int = 1):
    """3D training mesh (data, model, pipe) for the executable pipeline.

    ``pipe`` spans the 1F1B/GPipe stages (slowest-varying so stage
    neighbours sit on contiguous device spans), ``model`` the Megatron TP
    shards within each stage, ``data`` the ZeRO/data-parallel replicas.
    With pipe=1 this degenerates to the classic (data, model) layout plus a
    size-1 axis, so one code path serves 1D/2D/3D runs.
    """
    n = len(jax.devices())
    if data * model * pipe > n:
        raise ValueError(
            f"mesh {data}x{model}x{pipe} needs {data * model * pipe} "
            f"devices, have {n}"
        )
    return make_mesh((data, model, pipe), ("data", "model", "pipe"))


def make_serve_mesh(data: int = 1, model: int = 1):
    """Serving mesh: `model` shards one engine (TP), `data` counts replicas.

    Requires data*model visible devices (force with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU).
    """
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices, have {n}"
        )
    return make_mesh((data, model), ("data", "model"))


def replica_submeshes(mesh):
    """Split a ``(data, model)`` mesh into per-replica model-only meshes.

    Each data slice becomes an independent serving replica holding a full
    (TP-sharded) parameter copy; a mesh without a ``data`` axis is one
    replica.
    """
    from jax.sharding import Mesh

    names = tuple(mesh.axis_names)
    if "data" not in names or mesh.shape["data"] == 1:
        keep = [a for a in names if a != "data"] or list(names)
        devs = mesh.devices.reshape(
            tuple(mesh.shape[a] for a in keep)
        )
        return [Mesh(devs, tuple(keep))]
    d_axis = names.index("data")
    devs = mesh.devices
    out = []
    for i in range(mesh.shape["data"]):
        sl = [slice(None)] * devs.ndim
        sl[d_axis] = i
        keep = tuple(a for a in names if a != "data")
        out.append(Mesh(devs[tuple(sl)], keep))
    return out
