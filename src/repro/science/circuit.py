"""Circuit simulation [Bauer et al. 2012] (paper app 7) — distributed.

The Legion circuit benchmark: a graph of nodes (voltage, charge,
capacitance) and wires (resistance, current) partitioned into pieces.
Each timestep:

  1. calc_new_currents:  I_w = (V_src - V_dst) / R_w
  2. distribute_charge:  Q_n += dt * (sum of incident currents)
  3. update_voltages:    V_n += Q_n / C_n; Q_n = 0

Pieces own a contiguous slab of nodes and the wires sourced in the slab;
wires crossing piece boundaries make this communication-bound. The JAX
translation expresses the cross-piece reduction as all_gather(V) +
local scatter-add + psum_scatter(Q) — the all-reduce decomposition whose
placement Mapple's Region/decompose directives control.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.mapper import block_mapper
from repro.core.pspace import ProcSpace
from repro.matmul.common import MatmulGrid, build_grid

AXES = ("x",)


@dataclasses.dataclass(frozen=True)
class CircuitConfig:
    nodes_per_piece: int = 64
    wires_per_piece: int = 96
    pieces: int = 4
    pct_internal: float = 0.9      # fraction of wires that stay in-piece
    dt: float = 1e-2
    steps: int = 4

    @property
    def n_nodes(self) -> int:
        return self.nodes_per_piece * self.pieces

    @property
    def n_wires(self) -> int:
        return self.wires_per_piece * self.pieces


@dataclasses.dataclass
class CircuitState:
    voltage: jax.Array      # (n_nodes,)
    charge: jax.Array       # (n_nodes,)
    capacitance: jax.Array  # (n_nodes,)
    src: jax.Array          # (n_wires,) int32
    dst: jax.Array          # (n_wires,) int32
    resistance: jax.Array   # (n_wires,)


def generate(cfg: CircuitConfig, seed: int = 0) -> CircuitState:
    rng = np.random.default_rng(seed)
    n, w = cfg.n_nodes, cfg.n_wires
    # Wire i belongs to piece i // wires_per_piece and is sourced there;
    # its other end stays in the piece with probability pct_internal.
    lo = np.repeat(np.arange(cfg.pieces) * cfg.nodes_per_piece,
                   cfg.wires_per_piece)
    src = lo + rng.integers(cfg.nodes_per_piece, size=w)
    internal = rng.random(w) < cfg.pct_internal
    dst = np.where(internal, lo + rng.integers(cfg.nodes_per_piece, size=w),
                   rng.integers(n, size=w))
    src, dst = src.astype(np.int32), dst.astype(np.int32)
    return CircuitState(
        voltage=jnp.asarray(rng.normal(size=n).astype(np.float32)),
        charge=jnp.zeros(n, jnp.float32),
        capacitance=jnp.asarray(rng.uniform(1.0, 2.0, size=n).astype(np.float32)),
        src=jnp.asarray(src),
        dst=jnp.asarray(dst),
        resistance=jnp.asarray(rng.uniform(1.0, 4.0, size=w).astype(np.float32)),
    )


def grid_for(machine: ProcSpace, cfg: CircuitConfig, devices=None) -> MatmulGrid:
    m1 = machine.merge(0, 1) if machine.ndim == 2 else machine
    mapper = block_mapper(m1, "circuit_block")
    return build_grid(mapper, (cfg.pieces,), AXES, devices)


def circuit_body(cfg: CircuitConfig, n_pieces: int):
    n_nodes = cfg.n_nodes

    def body(volt, charge, cap, src, dst, res):
        def step(_, carry):
            volt_loc, charge_loc = carry
            volt_full = jax.lax.all_gather(volt_loc, "x", tiled=True)
            cur = (volt_full[src] - volt_full[dst]) / res
            acc = jnp.zeros((n_nodes,), jnp.float32)
            acc = acc.at[src].add(-cfg.dt * cur)
            acc = acc.at[dst].add(cfg.dt * cur)
            acc_loc = jax.lax.psum_scatter(
                acc, "x", scatter_dimension=0, tiled=True
            )
            charge_loc = charge_loc + acc_loc
            volt_loc = volt_loc + charge_loc / cap
            charge_loc = jnp.zeros_like(charge_loc)
            return (volt_loc, charge_loc)

        volt, charge = jax.lax.fori_loop(0, cfg.steps, step, (volt, charge))
        return volt

    return body


def run(state: CircuitState, grid: MatmulGrid, cfg: CircuitConfig) -> jax.Array:
    fn = jax.shard_map(
        circuit_body(cfg, grid.shape[0]),
        mesh=grid.mesh,
        in_specs=(P("x"), P("x"), P("x"), P("x"), P("x"), P("x")),
        out_specs=P("x"),
        check_vma=False,
    )
    return jax.jit(fn)(
        state.voltage, state.charge, state.capacitance,
        state.src, state.dst, state.resistance,
    )


def reference(state: CircuitState, cfg: CircuitConfig) -> jax.Array:
    """Pure-jnp oracle on one device."""
    volt, charge = state.voltage, state.charge
    for _ in range(cfg.steps):
        cur = (volt[state.src] - volt[state.dst]) / state.resistance
        charge = charge.at[state.src].add(-cfg.dt * cur)
        charge = charge.at[state.dst].add(cfg.dt * cur)
        volt = volt + charge / state.capacitance
        charge = jnp.zeros_like(charge)
    return volt
