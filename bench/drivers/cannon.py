"""Driver of the Cannon configurations: ``repro.matmul.cannon.matmul``.

One call is one step: the distributed product C = A @ B on the grid that the
registry app's Mapple program (``hierarchical_block2D``) maps onto the mesh.
"""
from __future__ import annotations

import numpy as np
from jax.sharding import NamedSharding

from bench.references import matmul as reference


class Driver:
    steps_per_call = 1

    def __init__(self, config: dict, seed: int, devices):
        """The program on one process per device of ``devices``."""
        from repro.apps import get
        from repro.matmul import cannon
        from repro.matmul.common import MatmulGrid

        self.config, self.seed = config, seed
        self.shape = (config["m"], config["k"], config["n"])
        plan = get(config["program"]).spmd_plan(len(devices), devices=devices)
        self.grid = MatmulGrid(mesh=plan.mesh, axis_names=plan.axis_names)
        self.device = devices[0]
        # A and B are the program's arg0 and arg1, sharded as it names them.
        sharding = NamedSharding(plan.mesh, plan.in_specs["arg0"])
        self.a, self.b = reference.operands(seed, *self.shape, sharding)
        self._matmul = cannon.matmul

    def call(self):
        return self._matmul(self.a, self.b, self.grid)

    def work(self) -> dict:
        """Per step: the product's FLOPs and the least HBM traffic (read A
        and B, write C), and per chip the FLOPs of its q local products of
        (m/q x k/q) @ (k/q x n/q) blocks."""
        m, k, n = self.shape
        q = self.grid.shape[0]
        return {"flops": 2 * m * k * n,
                "hbm_bytes": 4 * (m * k + k * n + m * n),
                "matmul_flops_per_chip": q * 2 * (m // q) * (k // q) * (n // q)}

    def check(self, outputs) -> list[dict]:
        host = [np.asarray(o) for o in outputs]
        outputs.clear()
        self.a = self.b = self.grid = None
        errs = reference.errors(host, self.seed, *self.shape, self.device)
        return [{"name": k, "value": v, "limit": self.config["check"][k]}
                for k, v in errs.items()]

    def control(self) -> dict[str, float]:
        """The numbers compared, read off the control in the program's place."""
        return reference.errors([], self.seed, *self.shape, self.device,
                                control=True)
