"""Production meshes.

Single pod: (16, 16) over ("data", "model") — 256 chips (one v5e pod's
worth for this exercise). Multi-pod: (2, 16, 16) over ("pod", "data",
"model") — 512 chips; the `pod` axis is DCN-scale and shards the batch
(hierarchical DCN data-parallelism, the standard cross-pod recipe), so
gradient all-reduces decompose into fast ICI reductions + one small DCN
phase, which is exactly how XLA lowers a reduce over ("pod", "data") with
this mesh ordering.

Functions, not module constants: importing this module must never touch
jax device state (device count is locked at first backend init — the
dry-run sets XLA_FLAGS before any import).
"""
from __future__ import annotations

import jax

from repro.core._compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_par: int = 1):
    """Whatever this host has — for tests and CPU examples."""
    n = len(jax.devices())
    assert n % model_par == 0
    return make_mesh((n // model_par, model_par), ("data", "model"))


def make_macro_mesh(n_devices: int | None = None):
    """1-D retrieval mesh over ("macro",): one device per group of DIRC
    macros. This is the mesh `ShardedDircIndex(parallelism="shard_map")`
    scores over — pass it as `build(..., mesh=...)` (or let the index
    default to all devices). `n_devices=None` uses every device.
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    return make_mesh((n,), ("macro",), devices=devs)


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
