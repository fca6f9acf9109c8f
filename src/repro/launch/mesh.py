"""Mesh construction.  Functions, not module constants — importing this
module never touches jax device state (jax locks the device count on
first backend init, and only dryrun.py is allowed to fake 512 devices)."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.configs.base import MeshConfig, MULTI_POD, SINGLE_POD


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """The one mesh constructor.  Every axis is ``Auto``: the sharding
    rules in ``repro.sharding`` are annotations the compiler propagates,
    not the explicit-sharding types ``jax.make_mesh`` defaults to.
    ``devices`` (a subset of the host's, in order) are laid out as
    given; without them the whole host is, in its physical order."""
    types = (AxisType.Auto,) * len(axes)
    if devices is not None:
        return Mesh(np.asarray(devices).reshape(tuple(shape)), tuple(axes),
                    axis_types=types)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def production_mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_mesh_from_config(mesh_cfg: MeshConfig) -> Mesh:
    """The first ``mesh_cfg.num_devices`` local devices in its shape."""
    devs = jax.devices()
    need = mesh_cfg.num_devices
    assert len(devs) >= need, f"need {need} devices, have {len(devs)}"
    return make_mesh(mesh_cfg.shape, mesh_cfg.axes, devices=devs[:need])
