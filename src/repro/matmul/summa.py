"""SUMMA [Van De Geijn & Watts 1997] on a (q, q) grid via shard_map.

Each of the q panel steps broadcasts the owning column's A panel along rows
and the owning row's B panel along columns (realized as masked psum — the
SPMD broadcast idiom), then accumulates the local product.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.mapper import Mapper, hierarchical_block_mapper
from repro.core.pspace import ProcSpace
from repro.matmul.common import (
    MatmulGrid,
    build_grid,
    local_matmul,
    sharded_matmul_wrapper,
)

AXES = ("x", "y")


def paper_mapper(machine: ProcSpace, grid_shape: tuple[int, int]) -> Mapper:
    return hierarchical_block_mapper(machine, grid_shape, name="summa_hb2d")


def grid_for(machine: ProcSpace, devices=None) -> MatmulGrid:
    n = machine.nprocs
    q = int(round(n ** 0.5))
    if q * q != n:
        raise ValueError(f"SUMMA (square variant) needs square device count, got {n}")
    mapper = paper_mapper(machine, (q, q))
    return build_grid(mapper, (q, q), AXES, devices)


def summa_body(q: int, use_kernel: bool = False):
    def body(a_blk: jax.Array, b_blk: jax.Array) -> jax.Array:
        row = jax.lax.axis_index("x")
        col = jax.lax.axis_index("y")
        c0 = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)

        def step(t, c):
            # Broadcast A panel from column t along each row.
            a_panel = jax.lax.psum(
                jnp.where(col == t, a_blk, jnp.zeros_like(a_blk)), "y"
            )
            # Broadcast B panel from row t along each column.
            b_panel = jax.lax.psum(
                jnp.where(row == t, b_blk, jnp.zeros_like(b_blk)), "x"
            )
            return c + local_matmul(a_panel, b_panel, use_kernel)

        c = jax.lax.fori_loop(0, q, step, c0)
        return c.astype(a_blk.dtype)

    return body


def matmul(a: jax.Array, b: jax.Array, grid: MatmulGrid,
           use_kernel: bool = False) -> jax.Array:
    q = grid.shape[0]
    fn = sharded_matmul_wrapper(
        grid,
        summa_body, (q, use_kernel),
        in_specs=(P("x", "y"), P("x", "y")),
        out_spec=P("x", "y"),
    )
    return fn(a, b)
