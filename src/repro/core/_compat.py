"""The one mesh constructor every in-repo caller goes through.

`jax.make_mesh` gives `Explicit` axes by default, and the sharding
annotations in this repo (`with_sharding_constraint` in
`models.sharding`, `shard_map` over the macro mesh) are written for
`Auto` axes, so every mesh is built here with `AxisType.Auto` on each
axis.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axis_names: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh of `shape` over `axis_names`, every axis `Auto`.

    `devices` restricts the mesh to an explicit device subset (in that
    order); None lets `jax.make_mesh` pick the device order. This is THE
    multi-device mesh entry point for retrieval (`core.sharded_index`)
    and serving (`launch.mesh`).
    """
    shape = tuple(int(s) for s in shape)
    names = tuple(axis_names)
    auto = (AxisType.Auto,) * len(names)
    if devices is None:
        return jax.make_mesh(shape, names, axis_types=auto)
    n = int(np.prod(shape))
    if len(devices) < n:
        raise ValueError(
            f"mesh shape {shape} needs {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(shape), names,
                axis_types=auto)
