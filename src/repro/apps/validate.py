"""Numeric validation at the registry's problem sizes: DSL-mapped meshes
drive the real kernels.

Each hook builds the Mesh from the app's *parsed Mapple program* (via
``Application.spmd_plan``) — not from the library mapper functions — so a
passing check certifies the whole pipeline: DSL text -> Mapper ->
translated device permutation -> shard_map kernel -> matches the
single-device reference under the hook's stated bound.

Problems run at the sizes the registry declares (``apps/definitions.py``:
a 4096^3 matmul, a 1024x8192 stencil, 2048x16384 PENNANT zones, a
16384-piece circuit). The app runs on the first ``procs`` devices JAX
reports, which must be ``procs`` distinct devices: for fake CPU devices set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before JAX starts.
"""
from __future__ import annotations

import functools
import time

#: Bound on ``max|out - ref| / max|ref|`` of the distributed matmuls. A
#: TPU's default float32 ``dot`` rounds its operands to bfloat16, which at
#: 4096^3 leaves ~2.3e-3 of the reference's largest entry.
MATMUL_RTOL = 1e-2
#: Absolute bounds of the science apps, whose fields are O(1).
STENCIL_ATOL = 1e-4
PENNANT_ATOL = 1e-4
CIRCUIT_ATOL = 1e-3
#: Time steps each science app runs before it is compared.
STEPS = 2


def _grid_for(app, procs: int):
    import jax

    from repro.matmul.common import MatmulGrid

    have = len(jax.devices())
    if have < procs:
        raise RuntimeError(f"needs {procs} devices, JAX has {have}")
    plan = app.spmd_plan(procs, devices=jax.devices()[:procs])
    return MatmulGrid(mesh=plan.mesh, axis_names=plan.axis_names)


@functools.lru_cache(maxsize=1)
def _matmul_case(m: int, k: int, n: int):
    """Inputs and their float64 host product, shared by the six apps."""
    import numpy as np

    from repro.matmul.common import make_inputs

    a, b = make_inputs(m, k, n, seed=0)
    ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    return a, b, ref


def _matmul(app, grid) -> dict:
    import numpy as np

    from repro.matmul import ALGORITHMS

    prob = app.meta["problem"]
    a, b, ref = _matmul_case(prob.m, prob.k, prob.n)
    out = np.asarray(ALGORITHMS[app.name].matmul(a, b, grid))
    err = float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))
    return {"max_err": err, "bound": MATMUL_RTOL, "err_kind": "rel"}


def _stencil(app, grid) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from repro.science import stencil2d

    nx, ny = app.meta["lengths"]
    cfg = stencil2d.StencilConfig(nx=nx, ny=ny, steps=STEPS)
    field = jnp.arange(nx * ny, dtype=jnp.float32).reshape(nx, ny) / (nx * ny)
    out = stencil2d.run(field, grid, cfg)
    ref = stencil2d.reference(field, cfg)
    err = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
    return {"max_err": err, "bound": STENCIL_ATOL, "err_kind": "abs"}


def _pennant(app, grid) -> dict:
    import numpy as np

    from repro.science import pennant

    nzx, nzy = app.meta["lengths"]
    cfg = pennant.PennantConfig(nzx=nzx, nzy=nzy, steps=STEPS)
    state = pennant.init_state(cfg, seed=0)
    outs = pennant.run(state, grid, cfg)
    refs = pennant.reference(state, cfg)
    err = max(
        float(np.max(np.abs(np.asarray(o) - np.asarray(r))))
        for o, r in zip(outs, refs)
    )
    return {"max_err": err, "bound": PENNANT_ATOL, "err_kind": "abs"}


def _circuit(app, grid) -> dict:
    import numpy as np

    from repro.science import circuit

    cfg = circuit.CircuitConfig(
        nodes_per_piece=app.meta["nodes_per_piece"],
        wires_per_piece=app.meta["wires_per_piece"],
        pieces=app.meta["pieces"], steps=STEPS)
    state = circuit.generate(cfg, seed=0)
    out = circuit.run(state, grid, cfg)
    ref = circuit.reference(state, cfg)
    err = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
    return {"max_err": err, "bound": CIRCUIT_ATOL, "err_kind": "abs"}


_HOOKS = {
    "matmul": _matmul,
    "stencil": _stencil,
    "pennant": _pennant,
    "circuit": _circuit,
}


def check_batched_equivalence(app, procs: int) -> None:
    """Certify the vectorized mapper path: the batched assignment grid must
    be bit-identical to the per-point interpreter before we trust the mesh
    built from it."""
    import numpy as np

    grid_shape = app.tile_grid(procs)
    mapper = app.mapper(procs)
    batched = mapper.assignment_grid(grid_shape, use_cache=False)
    scalar = mapper.assignment_grid(
        grid_shape, vectorized=False, use_cache=False
    )
    if not np.array_equal(batched, scalar):
        raise AssertionError(
            f"{app.name}: batched mapper evaluation diverges from the "
            f"per-point path on grid {grid_shape}"
        )


def run(app, procs: int | None = None) -> dict:
    """Execute one app's kernel under its DSL-derived mesh vs reference.

    Returns the error against the reference and its bound, the mesh's
    grid, how many distinct devices it spans, the device kind and the
    one-off wall time (compilation and reference included). ``ok`` needs
    the error inside the bound and the mesh on ``procs`` distinct devices.
    """
    n = app.procs(procs)
    check_batched_equivalence(app, n)
    grid = _grid_for(app, n)
    devices = list(grid.mesh.devices.flat)
    t0 = time.perf_counter()
    res = _HOOKS[app.validate](app, grid)
    res["wall_s"] = time.perf_counter() - t0
    res["distinct_devices"] = len({d.id for d in devices})
    res["device"] = devices[0].device_kind
    res["grid"] = grid.shape
    res["ok"] = (res["max_err"] <= res["bound"]
                 and res["distinct_devices"] == n)
    return res
