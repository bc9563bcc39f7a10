"""Production meshes.

Everything is a FUNCTION — importing this module never touches jax device
state (jax locks the backend/device count on first use, and the dry-run
must set XLA_FLAGS before that happens).

  single pod : (data=16, model=16)            — 256 chips (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     — 512 chips across 2 pods

The `pod` axis composes with `data` for pure cross-pod DP (the default
rules map logical 'batch' → ('pod', 'data')); the TP/EP axis never
crosses a pod boundary, keeping all-to-all / all-gather traffic on
intra-pod ICI and only DP all-reduce on the inter-pod links — the
standard multi-pod layout.

Every mesh is built with Auto axes: the sharding rules and the serving
``shard_map`` bodies place data through ``with_sharding_constraint`` /
``NamedSharding`` specs, which Explicit axes (``jax.make_mesh``'s
default) refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _short_devices(what: str, n: int, devices: list) -> RuntimeError:
    """The error for a mesh that needs more devices than the process
    sees: on the CPU backend the fix is forcing host devices, on an
    accelerator it is a larger slice."""
    platform = devices[0].platform if devices else "none"
    msg = f"{what} needs {n} devices, found {len(devices)} {platform} device(s)"
    if platform == "cpu":
        msg += (
            " — set XLA_FLAGS="
            f'"--xla_force_host_platform_device_count={n}" before importing jax'
        )
    return RuntimeError(msg)


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise _short_devices(f"mesh {dict(zip(axes, shape))}", n, devices)
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(shape),
        devices=devices[:n],
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small (data, model) mesh over the process's first devices: 1..8
    forced host devices in CPU tests, the chips of one host on a TPU."""
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got (data={data}, model={model})")
    return _make_mesh((data, model), ("data", "model"))
