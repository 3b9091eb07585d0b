"""Shared machinery for the six distributed matmul algorithms (paper Sec. 6).

Every algorithm is a `shard_map` program over a Mesh whose *device order is
produced by a Mapple mapper* (see repro.core.translate). The algorithms
differ in (a) the processor grid the mapper produces and (b) the collective
schedule of the body — exactly the paper's framing: the mapper is the
performance-critical, swappable part.

A grid keeps the programs built on it: each is built on the first call
with its (body factory, the factory's static arguments, specs) and reused
by every later call on that grid. The grid's mesh carries the device order,
so two mappers of one grid shape never share a program.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.mapper import Mapper
from repro.core.translate import mesh_from_mapper
from repro.runtime import tracing


@dataclasses.dataclass(frozen=True)
class MatmulGrid:
    """A processor grid + the mesh realizing a Mapple mapper on it, and the
    programs built on it so far (``sharded_matmul_wrapper``)."""

    mesh: Mesh
    axis_names: tuple[str, ...]
    programs: dict = dataclasses.field(default_factory=dict, compare=False,
                                       repr=False)

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.mesh.devices.shape)


def build_grid(
    mapper: Mapper,
    grid_shape: Sequence[int],
    axis_names: Sequence[str],
    devices: Sequence[Any] | None = None,
) -> MatmulGrid:
    mesh = mesh_from_mapper(mapper, grid_shape, axis_names, devices)
    return MatmulGrid(mesh=mesh, axis_names=tuple(axis_names))


def shift(x: jax.Array, axis_name: str, offset: int, axis_size: int) -> jax.Array:
    """Cyclic shift of blocks along a mesh axis (Cannon's systolic move)."""
    perm = [(i, (i + offset) % axis_size) for i in range(axis_size)]
    with jax.named_scope("shift"):
        return jax.lax.ppermute(x, axis_name, perm)


def skew(x: jax.Array, by_axis: str, along_axis: str, sizes: tuple[int, int],
         sign: int) -> jax.Array:
    """Cannon's initial alignment: block (i, j) -> (i, j - sign*i) etc.

    ``by_axis`` provides the row index i; blocks move ``sign * i`` steps
    along ``along_axis``.
    """
    i = jax.lax.axis_index(by_axis)
    n = sizes[1]

    # Data-dependent shift distance: implement as (n-1) single-step shifts
    # with a predicated copy (SPMD-safe; every device runs the same program).
    def body(step, val):
        moved = shift(val, along_axis, sign, n)
        keep = step >= i
        return jnp.where(keep, val, moved)

    with jax.named_scope("skew"):
        return jax.lax.fori_loop(0, n - 1, body, x)


def block_spec(*axes: str | None) -> P:
    return P(*axes)


def reference_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.asarray(a) @ np.asarray(b)


def make_inputs(m: int, k: int, n: int, seed: int = 0, dtype=jnp.float32
                ) -> tuple[jax.Array, jax.Array]:
    kA, kB = jax.random.split(jax.random.key(seed))
    a = jax.random.normal(kA, (m, k), dtype=dtype)
    b = jax.random.normal(kB, (k, n), dtype=dtype)
    return a, b


def local_matmul(a: jax.Array, b: jax.Array,
                 use_kernel: bool = False) -> jax.Array:
    """Local block product — the per-device compute hot spot.

    With ``use_kernel=True`` routes through the Pallas MXU kernel
    (repro.kernels.ops.matmul); default jnp.dot for portability.
    """
    with jax.named_scope("local_matmul"):
        if use_kernel:
            from repro.kernels import ops as kops

            return kops.matmul(a, b)
        return jnp.dot(a, b, preferred_element_type=jnp.float32)


def sharded_matmul_wrapper(
    grid: MatmulGrid,
    body_factory: Callable[..., Callable[..., jax.Array]],
    body_args: tuple,
    in_specs: tuple[P, ...],
    out_spec: P,
):
    """The jitted ``shard_map`` of ``body_factory(*body_args)`` over the
    grid's mesh, for the caller to call at once.

    Every call counts ``matmul.calls``. The first call on ``grid`` with a
    given (``body_factory``, ``body_args``, ``in_specs``, ``out_spec``)
    builds the program, counting ``matmul.builds`` under the span
    ``repro.matmul.build``; later calls return the same program, whose call
    takes jit's fast path: no trace, no lowering, no compile.
    """
    tracing.count("matmul.calls")
    key = (body_factory, body_args, in_specs, out_spec)
    fn = grid.programs.get(key)
    if fn is None:
        tracing.count("matmul.builds")
        with tracing.span("matmul.build"):
            fn = grid.programs[key] = jax.jit(jax.shard_map(
                body_factory(*body_args), mesh=grid.mesh, in_specs=in_specs,
                out_specs=out_spec, check_vma=False,
            ))
    return fn
