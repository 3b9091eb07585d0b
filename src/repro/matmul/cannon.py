"""Cannon's algorithm [Cannon 1969] on a (q, q) torus via shard_map.

Mapper: the paper's ``hierarchical_block2D`` (Fig. 12) — node-block over the
outer factors, cyclic over the intra-node factors. Swapping in the "runtime
heuristics" mapper (Fig. 13 strawman) changes only the Mesh device order,
and so the grid: each grid builds its program on its first call and reuses
it after (``common.sharded_matmul_wrapper``).
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
    shift,
    skew,
)

AXES = ("x", "y")


def paper_mapper(machine: ProcSpace, grid_shape: tuple[int, int]) -> Mapper:
    """Fig. 12: hierarchical_block2D over the (node, gpu) machine."""
    return hierarchical_block_mapper(machine, grid_shape, name="cannon_hb2d")


def grid_for(machine: ProcSpace, devices=None) -> MatmulGrid:
    n = machine.nprocs
    q = int(round(n ** 0.5))
    if q * q != n:
        raise ValueError(f"Cannon's algorithm needs a square device count, got {n}")
    mapper = paper_mapper(machine, (q, q))
    return build_grid(mapper, (q, q), AXES, devices)


def cannon_body(q: int, use_kernel: bool = False):
    def body(a_blk: jax.Array, b_blk: jax.Array) -> jax.Array:
        # Initial alignment: A row i shifts left i, B col j shifts up j.
        a_blk = skew(a_blk, by_axis="x", along_axis="y", sizes=(q, q), sign=-1)
        b_blk = skew(b_blk, by_axis="y", along_axis="x", sizes=(q, q), sign=-1)
        c0 = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)

        def step(_, carry):
            c, a, b = carry
            # Scope the accumulate too: XLA fuses it with the dot, and the
            # fusion may carry either op's name.
            with jax.named_scope("local_matmul"):
                c = c + local_matmul(a, b, use_kernel)
            a = shift(a, "y", -1, q)
            b = shift(b, "x", -1, q)
            return (c, a, b)

        c, _, _ = jax.lax.fori_loop(0, q, step, (c0, a_blk, b_blk))
        return c.astype(a_blk.dtype)

    return body


def matmul(a: jax.Array, b: jax.Array, grid: MatmulGrid,
           use_kernel: bool = False) -> jax.Array:
    q = grid.shape[0]
    fn = sharded_matmul_wrapper(
        grid,
        cannon_body, (q, use_kernel),
        in_specs=(P("x", "y"), P("x", "y")),
        out_spec=P("x", "y"),
    )
    return fn(a, b)
