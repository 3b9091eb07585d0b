"""PENNANT proxy [Ferenbaugh 2015] (paper app 9) — staggered-grid hydro.

The real PENNANT is unstructured-mesh Lagrangian hydrodynamics; this proxy
keeps its computational character — staggered zone/node variables,
predictor-corrector update, gather (zone->node forces) and scatter
(node->zone volumes) phases — on a structured 2D mesh so the distributed
data movement (halo exchange of zone pressures and corner forces) is the
same pattern Mapple's decompose optimizes.

State (zones are cells, nodes are cell corners):
  zone: density rho, specific internal energy e, pressure p (ideal gas)
  node: velocity (u, v) at cell corners (staggered)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.decompose import cached_optimal
from repro.core.mapper import block_mapper
from repro.core.pspace import ProcSpace
from repro.matmul.common import MatmulGrid, build_grid

AXES = ("x", "y")
GAMMA = 1.4


@dataclasses.dataclass(frozen=True)
class PennantConfig:
    nzx: int = 32          # zones in x
    nzy: int = 32          # zones in y
    dt: float = 1e-3
    dx: float = 1.0
    steps: int = 4


def grid_for(machine: ProcSpace, cfg: PennantConfig, devices=None) -> MatmulGrid:
    # Memoized + integrality-constrained (shards must tile the zone arrays).
    g = cached_optimal(machine.nprocs, (cfg.nzx, cfg.nzy), require_divisible=True)
    m1 = machine.merge(0, 1) if machine.ndim == 2 else machine
    m2 = m1.decompose_with(0, g)
    mapper = block_mapper(m2, "pennant_block")
    return build_grid(mapper, tuple(int(x) for x in g), AXES, devices)


def init_state(cfg: PennantConfig, seed: int = 0):
    key = jax.random.key(seed)
    k1, k2 = jax.random.split(key)
    rho = 1.0 + 0.1 * jax.random.uniform(k1, (cfg.nzx, cfg.nzy))
    e = 1.0 + 0.1 * jax.random.uniform(k2, (cfg.nzx, cfg.nzy))
    u = jnp.zeros((cfg.nzx, cfg.nzy))
    v = jnp.zeros((cfg.nzx, cfg.nzy))
    return rho.astype(jnp.float32), e.astype(jnp.float32), u.astype(jnp.float32), v.astype(jnp.float32)


def _halo1(f: jax.Array, axis_name: str, axis_size: int, dim: int):
    """1-deep edge-replicated halo along one sharded dimension."""
    idx = jax.lax.axis_index(axis_name)

    def take(x, lo, hi):
        sl = [slice(None)] * x.ndim
        sl[dim] = slice(lo, hi)
        return x[tuple(sl)]

    lo_face = take(f, 0, 1)
    hi_face = take(f, f.shape[dim] - 1, f.shape[dim])
    fwd = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    bwd = [(i, (i - 1) % axis_size) for i in range(axis_size)]
    from_prev = jax.lax.ppermute(hi_face, axis_name, fwd)
    from_next = jax.lax.ppermute(lo_face, axis_name, bwd)
    from_prev = jnp.where(idx == 0, lo_face, from_prev)
    from_next = jnp.where(idx == axis_size - 1, hi_face, from_next)
    return jnp.concatenate([from_prev, f, from_next], axis=dim)


def _padded(f, gx, gy):
    """Edge-replicated 1-halo in both dims (corners via sequential pad)."""
    f = _halo1(f, "x", gx, 0)
    f = _halo1(f, "y", gy, 1)
    return f


def hydro_step(rho, e, u, v, cfg: PennantConfig, gx: int, gy: int):
    # --- zone pressure (ideal gas EOS)
    p = (GAMMA - 1.0) * rho * e
    # --- gather phase: pressure gradient forces at nodes need neighbours
    p_pad = _padded(p, gx, gy)
    fx = -(p_pad[2:, 1:-1] - p_pad[:-2, 1:-1]) / (2.0 * cfg.dx)
    fy = -(p_pad[1:-1, 2:] - p_pad[1:-1, :-2]) / (2.0 * cfg.dx)
    # --- node (corner) velocity update
    u = u + cfg.dt * fx / rho
    v = v + cfg.dt * fy / rho
    # --- scatter phase: velocity divergence back onto zones
    u_pad = _padded(u, gx, gy)
    v_pad = _padded(v, gx, gy)
    div = (
        (u_pad[2:, 1:-1] - u_pad[:-2, 1:-1])
        + (v_pad[1:-1, 2:] - v_pad[1:-1, :-2])
    ) / (2.0 * cfg.dx)
    # --- Lagrangian density/energy update (compressible flow)
    rho = rho * (1.0 - cfg.dt * div)
    e = e - cfg.dt * p * div / jnp.maximum(rho, 1e-6)
    return rho, e, u, v


def pennant_body(cfg: PennantConfig, grid_shape):
    gx, gy = grid_shape

    def body(rho, e, u, v):
        def step(_, carry):
            return hydro_step(*carry, cfg, gx, gy)

        return jax.lax.fori_loop(0, cfg.steps, step, (rho, e, u, v))

    return body


def run(state, grid: MatmulGrid, cfg: PennantConfig):
    fn = jax.shard_map(
        pennant_body(cfg, grid.shape),
        mesh=grid.mesh,
        in_specs=(P("x", "y"),) * 4,
        out_specs=(P("x", "y"),) * 4,
        check_vma=False,
    )
    return jax.jit(fn)(*state)


def reference(state, cfg: PennantConfig):
    """Single-device oracle (identical math, jnp.pad halos)."""
    rho, e, u, v = state

    def pad(f):
        return jnp.pad(f, 1, mode="edge")

    for _ in range(cfg.steps):
        p = (GAMMA - 1.0) * rho * e
        pp = pad(p)
        fx = -(pp[2:, 1:-1] - pp[:-2, 1:-1]) / (2.0 * cfg.dx)
        fy = -(pp[1:-1, 2:] - pp[1:-1, :-2]) / (2.0 * cfg.dx)
        u = u + cfg.dt * fx / rho
        v = v + cfg.dt * fy / rho
        up, vp = pad(u), pad(v)
        div = (
            (up[2:, 1:-1] - up[:-2, 1:-1]) + (vp[1:-1, 2:] - vp[1:-1, :-2])
        ) / (2.0 * cfg.dx)
        rho = rho * (1.0 - cfg.dt * div)
        e = e - cfg.dt * p * div / jnp.maximum(rho, 1e-6)
    return rho, e, u, v
