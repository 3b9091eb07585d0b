"""Production meshes (a FUNCTION — importing this never touches devices).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16).

The device ORDER inside the mesh is a Mapple decision: by default the
identity (block) order; ``mapper_permutation`` applies a Mapple mapper's
tile->device map (Sec. 5 translation) before reshaping, which is how the
hillclimb experiments reorder collectives without touching model code.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def make_production_mesh(*, multi_pod: bool = False, devices=None,
                         permutation: Sequence[int] | None = None):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    devices = list(devices)[:n]
    if permutation is not None:
        devices = [devices[p] for p in permutation]
    dev_arr = np.asarray(devices, dtype=object).reshape(shape)
    return jax.sharding.Mesh(dev_arr, axes)


def mapper_permutation(mapper, grid_shape: Sequence[int]) -> np.ndarray:
    """Evaluate a Mapple mapper into a flat device permutation."""
    n = int(np.prod(tuple(grid_shape)))
    return mapper.tile_permutation(tuple(grid_shape), n)


def small_mesh(axis_names=("data", "model"), shape=None):
    """Mesh over whatever devices exist (tests / CPU examples)."""
    import jax

    devs = jax.devices()
    if shape is None:
        shape = (len(devs), 1)
    dev_arr = np.asarray(devs[: int(np.prod(shape))], dtype=object).reshape(shape)
    return jax.sharding.Mesh(dev_arr, axis_names)
