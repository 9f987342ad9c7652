"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.  The production target is a TPU v5e pod of
16x16 = 256 chips; the multi-pod configuration stacks 2 pods = 512 chips
with a leading "pod" mesh axis (data-center network between pods, ICI
within a pod).

Every axis is `AxisType.Auto`: `jax.make_mesh` defaults to `Explicit` axes,
under which `with_sharding_constraint` and the "chips" placement of
`repro.mc.shard_ensemble` are rejected.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(devices=None):
    """1D "data" mesh over `devices` (default: every device of this host)."""
    devices = jax.devices() if devices is None else list(devices)
    return _auto_mesh((len(devices),), ("data",), devices)
