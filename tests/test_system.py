"""End-to-end behaviour tests for the whole system.

DSL text -> mapper -> mesh translation -> distributed compute -> training
with checkpoint/restart — the full path a user takes.
"""
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def test_dsl_to_assignment_end_to_end():
    """A textual Mapple program drives an actual device assignment."""
    from repro.core import dsl

    prog = dsl.parse("""
m = Machine(GPU, shape=(2, 2))

def block2d(Tuple ipoint, Tuple ispace):
    idx = ipoint * m.size / ispace
    return m[*idx]

IndexTaskMap stencil block2d
Region stencil arg0 GPU FBMEM
Backpressure stencil 2
""")
    mapper = prog.mappers["block2d"]
    grid = mapper.assignment_grid((4, 4))
    # quadrant block assignment over 4 processors
    assert grid[0, 0] == grid[1, 1]
    assert len(np.unique(grid)) == 4
    assert mapper.is_bijective_on((2, 2), 4)
    assert prog.backpressure["stencil"] == 2


def test_paper_figures_numerics():
    """The numbers the paper derives must fall out of the implementation."""
    from repro.core import (
        greedy_factorization, halo_surface_volume, optimal_factorization,
    )
    from repro.core.decompose import count_factorizations

    # Fig. 8: 96 vs 84 boundary elements.
    assert 2 * halo_surface_volume((12, 18), greedy_factorization(6, 2)) == 96
    assert 2 * halo_surface_volume(
        (12, 18), optimal_factorization(6, (12, 18))
    ) == 84
    # Sec. 4.3: d=16, k=3 -> 15 factorizations; d=48 -> 45.
    assert count_factorizations(16, 3) == 15
    assert count_factorizations(48, 3) == 45
    # Sec. 4.3 closing example: d=72 over (8, 9) -> perfectly balanced.
    assert optimal_factorization(72, (8, 9)) == (8, 9)


def test_train_checkpoint_restart_cycle():
    """Supervisor restores from checkpoint after an injected failure and
    training completes with decreasing loss."""
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.data import make_pipeline
    from repro.models import build
    from repro.runtime import FailureInjector, Supervisor
    from repro.training import (
        AdamWConfig, TrainState, init_state, make_train_step,
    )

    cfg = get_config("smollm-135m").reduced()
    model = build(cfg)
    opt_cfg = AdamWConfig(lr=2e-3, warmup_steps=3, total_steps=40)
    pipe = make_pipeline(cfg, seq_len=32, global_batch=8)
    jitted = jax.jit(make_train_step(model, opt_cfg))

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)

        def step_fn(step, tree):
            st, metrics = jitted(TrainState.from_tree(tree), pipe.batch(step))
            return st.as_tree(), {k: float(v) for k, v in metrics.items()}

        state = init_state(model, jax.random.key(0), opt_cfg)
        sup = Supervisor(mgr)
        final, hist = sup.run(
            state=state.as_tree(), start_step=0, n_steps=20,
            step_fn=step_fn, save_every=5,
            injector=FailureInjector(fail_at_steps=(12,), max_failures=1),
        )
        losses = [h["loss"] for h in hist if "loss" in h]
        assert any("restored" in str(h.get("event", "")) for h in hist)
        assert losses[-1] < losses[0]


def test_autosharder_respects_constraints():
    from repro.core.autosharder import LMWorkload, plan_mesh

    wl = LMWorkload(global_batch=256, seq_len=4096, d_model=3584,
                    n_layers=28, n_heads=28, n_kv_heads=4, param_count=7.6e9)
    plan = plan_mesh(256, wl)
    assert plan.dp * plan.tp == 256
    assert 256 % plan.dp == 0
    # 28 heads: tp must divide 28 (or be 1)
    assert plan.tp == 1 or 28 % plan.tp == 0


@pytest.mark.slow
def test_dryrun_single_cell_compiles():
    """One full dry-run cell in a subprocess (512 fake devices)."""
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "smollm-135m", "--shape", "train_4k", "--mesh", "single"],
        capture_output=True, text=True, timeout=420, env=env, cwd=str(REPO),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "1 ok, 0 skipped, 0 errors" in proc.stdout


def test_elastic_restore_under_new_sharding():
    """Checkpoint written once restores under different shardings
    (mesh-agnostic restore — the elastic-rescale mechanism)."""
    from repro.checkpoint import CheckpointManager

    tree = {"w": jnp.arange(32.0).reshape(8, 4)}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, tree)
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model")
        )
        sh = {"w": jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, None))}
        step, restored, _ = mgr.restore(shardings=sh)
        assert restored["w"].sharding.is_equivalent_to(sh["w"], 2)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
