"""Tests for the unified nine-app registry and its end-to-end pipeline."""
import importlib.util
import math

import numpy as np
import pytest

from repro import apps
from repro.apps.definitions import (
    CIRCUIT_NODES_PER_PIECE,
    MATMUL_PROBLEM,
    PENNANT_FIELDS,
    PENNANT_ZONES,
    STENCIL_LENGTHS,
)
from repro.core.commvolume import (
    cannon_volume,
    halo_surface_volume,
    johnson_volume,
)
from repro.core.decompose import optimal_factorization

ALL_APPS = list(apps.iter_apps())
APP_IDS = [a.name for a in ALL_APPS]


def test_all_nine_paper_apps_registered():
    assert set(apps.names()) == {
        "cannon", "summa", "pumma", "johnson", "solomonik", "cosma",
        "circuit", "stencil", "pennant",
    }
    assert len(list(apps.iter_apps(kind=apps.MATMUL))) == 6
    assert len(list(apps.iter_apps(kind=apps.SCIENCE))) == 3


def test_registry_lookup_errors():
    with pytest.raises(KeyError):
        apps.get("nonexistent")
    with pytest.raises(ValueError):
        apps.register(apps.get("cannon"))  # duplicate name


@pytest.mark.parametrize("app", ALL_APPS, ids=APP_IDS)
def test_mapple_program_parses(app):
    prog = app.program()
    assert app.name in prog.index_task_maps
    mapper_name = prog.index_task_maps[app.name]
    assert mapper_name in prog.mappers
    assert prog.loc() > 0


@pytest.mark.parametrize("app", ALL_APPS, ids=APP_IDS)
def test_mapper_is_bijective_on_tile_grid(app):
    n = app.default_procs
    grid = app.tile_grid(n)
    assert math.prod(grid) == n
    assert app.mapper(n).is_bijective_on(grid, n)


@pytest.mark.parametrize("app", ALL_APPS, ids=APP_IDS)
def test_translate_produces_valid_permutation(app):
    plan = app.spmd_plan()
    n = plan.meta["nprocs"]
    perm = plan.meta["device_permutation"]
    assert sorted(perm) == list(range(n))
    assert plan.meta["task"] == app.name
    assert plan.axis_names == app.axis_names
    assert plan.backpressure >= 1


@pytest.mark.parametrize("app", ALL_APPS, ids=APP_IDS)
def test_mapple_matches_lowlevel_fixture(app):
    """The DSL program and the raw-JAX baseline express the same mapping."""
    spec = importlib.util.spec_from_file_location(
        f"{app.name}_raw_fixture", app.lowlevel_path()
    )
    raw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(raw)
    assert raw.MACHINE_SHAPE == app.machine_shape(app.default_procs)
    raw_grid = raw.assignment_grid(raw.GRID_SHAPE, raw.MACHINE_SHAPE)
    dsl_grid = app.mapper().assignment_grid(raw.GRID_SHAPE)
    np.testing.assert_array_equal(raw_grid, dsl_grid)


@pytest.mark.parametrize("app", ALL_APPS, ids=APP_IDS)
def test_loc_reduction_over_lowlevel(app):
    """Table 1's direction: the DSL program is several times smaller."""
    assert app.lowlevel_loc() / app.mapple_loc() > 2.0


def test_comm_volume_closed_forms():
    """Registry volumes equal independently computed closed forms."""
    # Cannon on (2, 2): q*q*(q-1)*(tile_a+tile_b).
    p = MATMUL_PROBLEM
    assert apps.get("cannon").comm_volume(4) == pytest.approx(
        cannon_volume(p, (2, 2))
    )
    assert apps.get("johnson").comm_volume(8) == pytest.approx(
        johnson_volume(p, (2, 2, 2))
    )
    # Stencil: Sec. 4.2 interior-surface volume at the decompose grid.
    g = optimal_factorization(8, STENCIL_LENGTHS)
    assert apps.get("stencil").comm_volume(8) == pytest.approx(
        halo_surface_volume(STENCIL_LENGTHS, g)
    )
    # cut counting for a (1, 8) slab grid: 7 interior cuts of l0 elements
    assert halo_surface_volume(STENCIL_LENGTHS, (1, 8)) == pytest.approx(
        7 * STENCIL_LENGTHS[0]
    )
    # Pennant: 3 exchanged fields scale the halo volume.
    gp = optimal_factorization(8, PENNANT_ZONES)
    assert apps.get("pennant").comm_volume(8) == pytest.approx(
        PENNANT_FIELDS * halo_surface_volume(PENNANT_ZONES, gp)
    )
    # Circuit: all_gather + psum_scatter ring volume, 2*(p-1)*n elements.
    assert apps.get("circuit").comm_volume(8) == pytest.approx(
        2 * 7 * 8 * CIRCUIT_NODES_PER_PIECE
    )


def test_tuning_never_worse_than_default():
    for app in ALL_APPS:
        v_def, v_tuned = app.tuning(app.default_procs)
        assert v_tuned <= v_def * (1 + 1e-9), app.name


def test_invalid_proc_counts_rejected():
    with pytest.raises(ValueError):
        apps.get("cannon").tile_grid(6)       # not square
    with pytest.raises(ValueError):
        apps.get("johnson").tile_grid(16)     # not cubic


def test_scaling_to_larger_machines():
    """Every app that accepts 64 processors stays bijective there."""
    for app in ALL_APPS:
        plan = app.spmd_plan(64)
        perm = plan.meta["device_permutation"]
        assert sorted(perm) == list(range(64)), app.name


def test_directives_reach_the_plan():
    plan = apps.get("circuit").spmd_plan()
    assert plan.memory_kinds["arg1"] == "pinned_host"   # Region ... ZCMEM
    cannon = apps.get("cannon").spmd_plan()
    assert cannon.donate == ("arg2",)                   # GarbageCollect
    assert cannon.backpressure == 1                     # Backpressure


def test_run_cli_all_analysis():
    """`python -m repro.apps.run --all` end to end (analysis path)."""
    from repro.apps import run as apprun

    assert apprun.main(["--all"]) == 0
    assert apprun.main(["--app", "summa", "--procs", "64"]) == 0


@pytest.mark.slow
def test_run_cli_execute_subprocess(tmp_path):
    """Full numeric validation of all nine apps, at the registry's sizes,
    on eight fake CPU devices that the caller (not the CLI) asks for."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = tmp_path / "execute.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.apps.run", "--all", "--execute",
         "--json", str(out)],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(repo),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = json.loads(out.read_text())["apps"]
    assert len(rows) == 9
    for row in rows:
        assert row["ok"] and row["max_err"] <= row["bound"], row
        assert row["distinct_devices"] == row["procs"], row
