"""The JAX pricing backend vs the NumPy batch engine.

The contract under test: ``JaxBatchSimulator`` returns the NumPy
engine's numbers — to float64 round-off in its default dtype, on every
formulation (dense gather, segment scatter, Pallas reduce), for any
placement (bijective or not), regardless of the NumPy side's folding /
incremental flags — while pricing whole stacks as compiled programs.
"""
import numpy as np
import pytest

from repro import apps
from repro.sim import jax_backend as jb
from repro.sim.batch import price_stacks
from repro.sim.cost import SimulatedTimeCostModel, time_search_space

# float64 (the default) reproduces the NumPy engine to round-off; the
# registry parity gate in benchmarks/sim_eval.py runs at 1e-6 relative.
F64_RTOL = 1e-12
# float32 accumulates port loads in single precision: fine for search
# ranking, NOT for the parity gate (use float64 there) — see
# docs/simulator.md "Backends".
F32_RTOL = 5e-4


def _model(app_name: str, opts: dict | None = None):
    app = apps.get(app_name)
    sp = time_search_space(app)
    combo = dict(next(iter(app.search_space.option_combos())))
    n = app.default_procs
    model = sp.cost_model(n, opts if opts is not None else combo)
    grid = next(g for g in app.search_space.grids(n))
    return model, grid, n


def _stack(model, grid, n, n_rand: int = 4, seed: int = 0):
    rng = np.random.default_rng(seed)
    rows = [model._default_assignment(grid).reshape(-1)]
    rows += [rng.permutation(n) for _ in range(n_rand)]
    return np.stack(rows)


def _rel(got, ref):
    return float((np.abs(got - ref)
                  / np.maximum(np.abs(ref), 1e-300)).max())


@pytest.mark.parametrize("app_name", ["summa", "stencil", "circuit",
                                      "solomonik"])
def test_f64_parity_vs_numpy_fold_on_and_off(app_name):
    model, grid, n = _model(app_name)
    eng = model.batch(grid)
    stack = _stack(model, grid, n)
    got = jb.to_jax(eng).step_times(stack)
    for fold in (True, False):
        ref = eng.step_times(stack, fold=fold, incremental=fold)
        assert _rel(got, ref) <= F64_RTOL


@pytest.mark.parametrize("app_name", ["summa", "stencil"])
def test_scatter_mode_parity(app_name, monkeypatch):
    """With the dense ceiling forced to zero every schedule takes the
    general segment-scatter formulation — same numbers."""
    monkeypatch.setattr(jb, "_DENSE_CELLS_MAX", 0)
    model, grid, n = _model(app_name)
    eng = model.batch(grid)
    stack = _stack(model, grid, n)
    jeng = jb.to_jax(eng)
    # The export is memoized on the (shared, memoized) schedule object —
    # drop any dense export a previous pricing left there.
    getattr(jeng.schedule, "_jax_exports", {}).clear()
    got = jeng.step_times(stack)
    exp = jb._export_for(jeng.schedule, jeng.topology)
    assert exp.mode == "scatter"
    assert _rel(got, eng.step_times(stack)) <= F64_RTOL
    getattr(jeng.schedule, "_jax_exports", {}).clear()


def test_pallas_reduce_parity():
    """The Pallas reduce runs in float32 (Mosaic has no float64), so it
    matches the NumPy engine to the float32 tolerance — and the plain
    float32 jit to a few ulps."""
    model, grid, n = _model("summa")
    eng = model.batch(grid)
    stack = _stack(model, grid, n)
    ref = eng.step_times(stack)
    got = jb.to_jax(eng, dtype="float32", use_pallas=True).step_times(stack)
    plain = jb.to_jax(eng, dtype="float32").step_times(stack)
    assert _rel(got, ref) <= F32_RTOL
    assert _rel(got, plain) <= 1e-6


def test_pallas_float64_refused():
    model, grid, n = _model("summa")
    with pytest.raises(ValueError, match="float32"):
        jb.to_jax(model.batch(grid), use_pallas=True)


def test_f32_is_looser_than_f64():
    """The dtype boundary: float32 drifts past float64 round-off (single
    -precision port-load accumulation) but stays inside the documented
    search-ranking tolerance. Anything needing the 1e-6 parity gate must
    run float64."""
    model, grid, n = _model("summa")
    eng = model.batch(grid)
    stack = _stack(model, grid, n)
    ref = eng.step_times(stack)
    rel32 = _rel(jb.to_jax(eng, dtype="float32").step_times(stack), ref)
    rel64 = _rel(jb.to_jax(eng).step_times(stack), ref)
    assert rel64 <= F64_RTOL
    assert rel32 <= F32_RTOL
    assert rel32 > rel64          # f32 really is the lossy tier


def test_non_bijective_rows_fall_back_to_scatter():
    """Dense mode needs invertible rows; a stack with repeated target
    processors must still price exactly (via the scatter formulation)."""
    model, grid, n = _model("stencil")
    eng = model.batch(grid)
    bad = np.tile(np.arange(n) // 2 * 2, (3, 1))
    ref = eng.step_times(bad)
    got = jb.to_jax(eng).step_times(bad)
    assert _rel(got, ref) <= F64_RTOL


def test_fold_flags_are_moot():
    model, grid, n = _model("summa")
    jeng = jb.to_jax(model.batch(grid))
    stack = _stack(model, grid, n)
    a = jeng.step_times(stack)
    b = jeng.step_times(stack, fold=False, incremental=False)
    np.testing.assert_array_equal(a, b)


def test_chunked_pricing_matches_single_call(monkeypatch):
    """Shrinking the device budget forces multiple padded chunks; the
    result must be bit-identical to the one-chunk pricing."""
    model, grid, n = _model("summa")
    eng = model.batch(grid)
    stack = _stack(model, grid, n, n_rand=6)
    whole = jb.to_jax(eng).step_times(stack)
    monkeypatch.setattr(jb, "_MAX_DEVICE_ELEMS", 1)
    jeng = jb.to_jax(eng)
    jb._export_for(jeng.schedule, jeng.topology)._fns.clear()
    chunked = jeng.step_times(stack)
    np.testing.assert_array_equal(whole, chunked)


def test_price_stacks_routes_jax_engines():
    """Mixed numpy/jax stacks through one price_stacks call: the jax
    engine prices independently, the numpy engine joins the shared pass,
    and both return the same seconds."""
    model, grid, n = _model("stencil")
    eng = model.batch(grid)
    jeng = jb.to_jax(eng)
    stack = _stack(model, grid, n)
    out_np, out_jax = price_stacks([(eng, stack), (jeng, stack)])
    assert _rel(out_jax, out_np) <= F64_RTOL


def test_cost_model_engine_batched_jax():
    model, grid, n = _model("summa")
    jmodel = SimulatedTimeCostModel(
        pattern=model.pattern, spec=model.spec,
        step_flops=model.step_flops, base=model.base,
        engine="batched-jax",
    )
    assert isinstance(jmodel.beam_pricer(grid), jb.JaxBatchSimulator)
    assert abs(jmodel.cost(grid) - model.cost(grid)) \
        <= F64_RTOL * abs(model.cost(grid))
    got = jmodel.price_assignments(grid, _stack(model, grid, n))
    ref = model.price_assignments(grid, _stack(model, grid, n))
    assert _rel(got, ref) <= F64_RTOL


def test_cost_model_rejects_unknown_engine():
    model, grid, n = _model("summa")
    with pytest.raises(ValueError, match="engine"):
        SimulatedTimeCostModel(
            pattern=model.pattern, spec=model.spec,
            step_flops=model.step_flops, engine="batched-tpu",
        )


def test_invalid_dtype_rejected():
    model, grid, n = _model("summa")
    with pytest.raises(ValueError, match="dtype"):
        jb.to_jax(model.batch(grid), dtype="float16")


def test_tuner_picks_same_winner_on_jax_engine():
    """End to end: the autotuner searching on the jax engine lands on
    the same winning candidate as on the numpy engine."""
    from repro.search.tuner import tune_app
    from repro.sim.cost import time_tuned_app

    app = apps.get("summa")
    rep_np = tune_app(time_tuned_app(app), None)
    rep_jax = tune_app(time_tuned_app(app, engine="batched-jax"), None)
    assert (rep_jax.best.candidate.describe()
            == rep_np.best.candidate.describe())
    assert rep_jax.best.placed_cost == pytest.approx(
        rep_np.best.placed_cost, rel=1e-9)


def test_cli_backend_flag():
    from repro.apps.run import main

    assert main(["--app", "summa", "--tune", "--time",
                 "--backend", "jax"]) == 0
    with pytest.raises(SystemExit):
        main(["--app", "summa", "--tune", "--backend", "jax"])


def test_export_cached_on_schedule():
    model, grid, n = _model("stencil")
    jeng = jb.to_jax(model.batch(grid))
    jeng.step_times(_stack(model, grid, n, n_rand=1))
    e1 = jb._export_for(jeng.schedule, jeng.topology)
    e2 = jb._export_for(jeng.schedule, jeng.topology)
    assert e1 is e2
    assert e1._fns              # compiled callables retained
