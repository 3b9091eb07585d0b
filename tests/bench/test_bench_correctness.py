"""The comparison that decides ``correct``, at sizes the CPU holds.

Every cell of ``BENCHMARK.json`` runs at its configuration's ``cpu_sizes``
on four CPU devices through the harness (its look for a chip skipped):
sound, ``correct`` is true; with the control, the reference in the
precision below the configuration's, in the program's place, or with the
timed path broken underneath in each way that its driver's
``tests/bench/faults/<driver>.py`` plants on the cell's chips, it is false.
The control's readings, as ``bench/calibrate.py`` takes them, lie above a
limit of the configuration.
"""
import json

import pytest

from bench_helpers import cell_faults, read_spec, run_python, write_tiny_bench

CELLS = [w["name"] for w in read_spec()["workloads"]]
FAULTS = cell_faults()


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tiny")
    spec = write_tiny_bench(tmp)
    scenarios = [f"{k}:{c}" for k in ("sound", "readings", "control")
                 for c in CELLS] + [f"{k}:{c}" for k, c in FAULTS]
    proc = run_python(["tests/bench/run_tiny.py", str(spec), *scenarios], tmp)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return {(r["scenario"], r["cell"]): r for r in rows}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(results, cell):
    r = results[("sound", cell)]
    assert r["correct"], r["check"]
    assert r["calls"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(results, cell):
    r = results[("readings", cell)]
    assert any(r["readings"][k] > r["limits"][k] for k in r["limits"]), r


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(results, cell):
    r = results[("control", cell)]
    assert not r["correct"], r["check"]


@pytest.mark.parametrize("fault,cell", FAULTS)
def test_broken_timed_path_is_not_correct(results, fault, cell):
    r = results[(fault, cell)]
    assert not r["correct"], r["check"]


@pytest.mark.parametrize("cell,expected", [
    # Cannon, m = k = n = 256: 2 m k n FLOPs, A, B and C once each in f32;
    # on a 2x2 grid each chip makes 2 products of 128^3.
    ("cannon-16384.x1", {"flops": 33554432, "hbm_bytes": 786432,
                         "matmul_flops_per_chip": 33554432}),
    ("cannon-16384.x4", {"flops": 33554432, "hbm_bytes": 786432,
                         "matmul_flops_per_chip": 2 * 2 * 128 ** 3}),
])
def test_work_counted_from_shapes(results, cell, expected):
    assert results[("sound", cell)]["work"] == expected


def test_control_rounds_operands_as_float8_e4m3():
    """The Cannon control rounds its operands by arithmetic to float8
    e4m3's precision; over e4m3's normal range that is the cast itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.references.matmul import round_mantissa

    x = jax.random.normal(jax.random.key(0), (4096,), jnp.float32) * 8
    x = x[jnp.abs(x) >= 2.0 ** -6]
    cast = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    assert np.array_equal(np.asarray(round_mantissa(x)), np.asarray(cast))
