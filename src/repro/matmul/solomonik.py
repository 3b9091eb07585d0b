"""Solomonik's 2.5D algorithm [Solomonik & Demmel 2011] on a (q, q, c) grid.

c replicas of the Cannon schedule each execute q/c shift steps starting from
layer-offset alignments; a final psum over the replication axis combines the
partial C blocks. Mappers: the paper's ``hierarchical_block3D`` +
``linearize_cyclic`` pair (Fig. 12, Solomonik functions 1 and 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.mapper import (
    Mapper,
    hierarchical_block_mapper,
    linearize_cyclic_mapper,
)
from repro.core.pspace import ProcSpace
from repro.matmul.common import (
    MatmulGrid,
    build_grid,
    local_matmul,
    sharded_matmul_wrapper,
    shift,
)

AXES = ("x", "y", "z")


def grid_shape_for(nprocs: int, c: int) -> tuple[int, int, int]:
    base = nprocs // c
    q = int(round(base ** 0.5))
    if q * q * c != nprocs:
        raise ValueError(f"cannot form (q, q, {c}) grid from {nprocs} devices")
    return (q, q, c)


def paper_mapper(machine: ProcSpace, grid_shape: tuple[int, int, int]) -> Mapper:
    """Fig. 12 function 1: hierarchical block over the 3D iteration grid."""
    return hierarchical_block_mapper(machine, grid_shape, name="solomonik_hb3d")


def fallback_mapper(machine: ProcSpace) -> Mapper:
    """Fig. 12 function 2: linearize + cyclic (used for tuning comparisons)."""
    return linearize_cyclic_mapper(machine)


def grid_for(machine: ProcSpace, c: int, devices=None,
             use_fallback_mapper: bool = False) -> MatmulGrid:
    g = grid_shape_for(machine.nprocs, c)
    mapper = (
        fallback_mapper(machine)
        if use_fallback_mapper
        else paper_mapper(machine, g)
    )
    return build_grid(mapper, g, AXES, devices)


def masked_shift(x: jax.Array, axis: str, steps: jax.Array, size: int) -> jax.Array:
    """Shift ``x`` by a device-dependent number of single steps (<= size-1)."""

    def body(s, val):
        moved = shift(val, axis, -1, size)
        return jnp.where(s < steps, moved, val)

    return jax.lax.fori_loop(0, size - 1, body, x)


def solomonik_body(q: int, c: int, use_kernel: bool = False):
    steps_per_layer = q // c

    def body(a_blk: jax.Array, b_blk: jax.Array) -> jax.Array:
        i = jax.lax.axis_index("x")
        j = jax.lax.axis_index("y")
        layer = jax.lax.axis_index("z")
        # Cannon-style alignment plus the layer offset l * (q/c).
        a_blk = masked_shift(a_blk, "y", (i + layer * steps_per_layer) % q, q)
        b_blk = masked_shift(b_blk, "x", (j + layer * steps_per_layer) % q, q)
        c0 = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)

        def step(_, carry):
            acc, a, b = carry
            acc = acc + local_matmul(a, b, use_kernel)
            a = shift(a, "y", -1, q)
            b = shift(b, "x", -1, q)
            return (acc, a, b)

        acc, _, _ = jax.lax.fori_loop(0, steps_per_layer, step, (c0, a_blk, b_blk))
        # Combine the c partial C replicas.
        acc = jax.lax.psum(acc, "z")
        return acc.astype(a_blk.dtype)

    return body


def matmul(a: jax.Array, b: jax.Array, grid: MatmulGrid,
           use_kernel: bool = False) -> jax.Array:
    q, _, c = grid.shape
    if q % c != 0:
        raise ValueError(f"2.5D requires c | q, got q={q}, c={c}")
    fn = sharded_matmul_wrapper(
        grid,
        solomonik_body, (q, c, use_kernel),
        # A, B block-distributed over (x, y), replicated over z.
        in_specs=(P("x", "y"), P("x", "y")),
        out_spec=P("x", "y"),
    )
    return fn(a, b)
