"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the ``pod``
axis is pure data parallelism over DCN; growing it is how the deployment
scales to N pods (the gradient all-reduce decomposes hierarchically:
reduce-scatter inside the pod over ICI, all-reduce across pods over DCN on
1/(data*model) of the bytes, all-gather inside the pod).

Defined as functions, not module constants, so importing this module never
touches jax device state (smoke tests run on 1 CPU device; only dryrun.py
forces 512 host devices).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """jax.make_mesh with Auto axes: GSPMD propagates shardings from the
    captured constraints (jax's default for make_mesh is Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over whatever devices exist (tests use
    XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def shrink_mesh(mesh, failed_device_id: int):
    """Rebuild ``mesh`` without the slice of devices containing
    ``failed_device_id``.

    The failed device's row is dropped along the outermost shrinkable
    axis — ``pod`` if present and >1, else ``data`` — which preserves the
    ``model`` axis size, so every TP-sharded dimension keeps dividing and
    existing NamedSharding specs stay valid on the new mesh.  Raises if
    the device is not in the mesh or no data-parallel axis can shrink
    (a pure-TP mesh cannot lose a device and keep the layout)."""
    import numpy as np

    devs = np.asarray(mesh.devices)
    ids = np.vectorize(lambda d: d.id)(devs)
    pos = np.argwhere(ids == failed_device_id)
    if pos.size == 0:
        raise ValueError(
            f"device {failed_device_id} not in mesh {mesh.axis_names}")
    axis_names = tuple(mesh.axis_names)
    for ax, name in enumerate(axis_names):
        if name != "model" and devs.shape[ax] > 1:
            keep = [i for i in range(devs.shape[ax]) if i != pos[0][ax]]
            new_devs = np.take(devs, keep, axis=ax)
            return jax.sharding.Mesh(new_devs, axis_names,
                                     axis_types=mesh.axis_types)
    raise ValueError(
        f"mesh {dict(zip(axis_names, devs.shape))} has no shrinkable "
        "data axis; cannot evict a device without breaking TP layout")
