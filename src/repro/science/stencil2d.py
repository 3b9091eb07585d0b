"""2D stencil benchmark [Van der Wijngaart & Mattson 2014] (paper app 8).

A 5-point Jacobi stencil over an (X, Y) grid, distributed over a 2D
processor grid chosen by Mapple's ``decompose`` (the paper's Sec. 6.3
workload). Halo exchange is a pair of ppermutes per dimension; the
communication volume is exactly the quantity decompose minimizes, so this
app is the end-to-end validation of the primitive.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.decompose import cached_optimal, greedy_factorization
from repro.core.mapper import block_mapper
from repro.core.pspace import ProcSpace
from repro.matmul.common import build_grid, MatmulGrid

AXES = ("x", "y")


@dataclasses.dataclass(frozen=True)
class StencilConfig:
    nx: int
    ny: int
    halo: int = 1
    steps: int = 4


def choose_grid(nprocs: int, cfg: StencilConfig, *, use_greedy: bool = False
                ) -> tuple[int, int]:
    """The experiment knob of Sec. 6.3: decompose vs Algorithm 1."""
    if use_greedy:
        g = greedy_factorization(nprocs, 2)
    else:
        # Memoized + integrality-constrained: shard_map needs every factor
        # to divide its extent (the paper's l_m/w_m in N constraint).
        g = cached_optimal(nprocs, (cfg.nx, cfg.ny), require_divisible=True)
    return (int(g[0]), int(g[1]))


def grid_for(machine: ProcSpace, cfg: StencilConfig, devices=None,
             use_greedy: bool = False) -> MatmulGrid:
    shape = choose_grid(machine.nprocs, cfg, use_greedy=use_greedy)
    m2 = machine.merge(0, 1).decompose_with(0, shape) if machine.ndim == 2 \
        else machine.decompose_with(0, shape)
    mapper = block_mapper(m2, "stencil_block")
    return build_grid(mapper, shape, AXES, devices)


def _exchange(field: jax.Array, axis_name: str, axis_size: int, dim: int,
              halo: int) -> tuple[jax.Array, jax.Array]:
    """Receive the neighbouring halo slabs along one dimension."""
    idx = jax.lax.axis_index(axis_name)

    def take(x, lo, hi):
        sl = [slice(None)] * x.ndim
        sl[dim] = slice(lo, hi)
        return x[tuple(sl)]

    # Send my low face to the left neighbour; receive from the right, etc.
    lo_face = take(field, 0, halo)
    hi_face = take(field, field.shape[dim] - halo, field.shape[dim])
    right = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    left = [(i, (i - 1) % axis_size) for i in range(axis_size)]
    from_left = jax.lax.ppermute(hi_face, axis_name, right)
    from_right = jax.lax.ppermute(lo_face, axis_name, left)
    # Zero-flux boundary at the global edges.
    from_left = jnp.where(idx == 0, lo_face, from_left)
    from_right = jnp.where(idx == axis_size - 1, hi_face, from_right)
    return from_left, from_right


def stencil_body(grid_shape: tuple[int, int], cfg: StencilConfig):
    gx, gy = grid_shape

    def body(field: jax.Array) -> jax.Array:
        def step(_, f):
            up, down = _exchange(f, "x", gx, 0, cfg.halo)
            left, right = _exchange(f, "y", gy, 1, cfg.halo)
            fx = jnp.concatenate([up, f, down], axis=0)
            f_pad = jnp.concatenate(
                [
                    jnp.pad(left, ((cfg.halo, cfg.halo), (0, 0)), mode="edge"),
                    fx,
                    jnp.pad(right, ((cfg.halo, cfg.halo), (0, 0)), mode="edge"),
                ],
                axis=1,
            )
            c = f_pad[1:-1, 1:-1]
            n = f_pad[:-2, 1:-1]
            s = f_pad[2:, 1:-1]
            w = f_pad[1:-1, :-2]
            e = f_pad[1:-1, 2:]
            return 0.2 * (c + n + s + w + e)

        return jax.lax.fori_loop(0, cfg.steps, step, field)

    return body


def run(field: jax.Array, grid: MatmulGrid, cfg: StencilConfig) -> jax.Array:
    body = stencil_body(grid.shape, cfg)  # type: ignore[arg-type]
    fn = jax.shard_map(
        body, mesh=grid.mesh, in_specs=(P("x", "y"),), out_specs=P("x", "y"),
        check_vma=False,
    )
    return jax.jit(fn)(field)


def reference(field, cfg: StencilConfig):
    """Pure-jnp oracle with zero-flux (edge-replicate) boundaries."""
    f = jnp.asarray(field)
    for _ in range(cfg.steps):
        fp = jnp.pad(f, cfg.halo, mode="edge")
        f = 0.2 * (
            fp[1:-1, 1:-1] + fp[:-2, 1:-1] + fp[2:, 1:-1]
            + fp[1:-1, :-2] + fp[1:-1, 2:]
        )
    return f
