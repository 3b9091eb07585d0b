"""Tests for the streaming Phase-3 pipeline (repro.search.pipeline).

The pipeline's contract is "reorders work, never arithmetic": every
number a pipelined tune produces must equal the barrier path's — bit for
bit in f64, and bit for bit between the async and synchronous entry
points of the same engine in f32 (the f32-vs-f64 drift belongs to the
engine, not the pipeline). Plus the mechanics: the bounded queue must
actually bound the producer's lead, producer exceptions must surface in
the consumer, and early consumer exit must unwind the producer thread.
"""
import threading
import time

import numpy as np
import pytest

from repro import apps
from repro.sim.batch import batch_simulator, price_stacks
from repro.sim.cost import time_tuned_app
from repro.sim.jax_backend import to_jax
from repro.search.pipeline import PriceJob, price_job, stream_priced
from repro.search.tuner import tune_app

TIMED_APPS = [a for a in apps.iter_apps()
              if a.search_space is not None
              and getattr(a, "collective", None) is not None]
APP_IDS = [a.name for a in TIMED_APPS]


def _leaderboard_key(report):
    return [(s.candidate.describe(), s.volume, s.placed_cost,
             s.cross_node, s.bijective) for s in report.leaderboard]


# ------------------------------------------------------------- bit identity
@pytest.mark.parametrize("app", TIMED_APPS, ids=APP_IDS)
def test_pipeline_matches_barrier_across_registry_jax(app):
    """Pipelined and barrier Phase 3 rank identically on the JAX engine:
    same winner, same leaderboard, placed seconds equal to the last
    bit (f64)."""
    timed = time_tuned_app(app, engine="batched-jax")
    streamed = tune_app(timed, pipeline=True)
    barrier = tune_app(timed, pipeline=False)
    assert streamed.best.candidate.describe() \
        == barrier.best.candidate.describe()
    assert _leaderboard_key(streamed) == _leaderboard_key(barrier)


@pytest.mark.parametrize("app", TIMED_APPS[:3], ids=APP_IDS[:3])
def test_pipeline_matches_barrier_numpy_engine(app):
    """The host NumPy engine streams too (eager handles): identical
    reports either way."""
    timed = time_tuned_app(app, engine="batched")
    streamed = tune_app(timed, pipeline=True)
    barrier = tune_app(timed, pipeline=False)
    assert _leaderboard_key(streamed) == _leaderboard_key(barrier)


def _stack_jobs(engine, rng, n_groups=4, rows=6):
    nt = int(np.prod(engine.schedule.grid))
    return [
        PriceJob(engine=engine,
                 stack=np.stack([rng.permutation(nt)
                                 for _ in range(rows)]),
                 entries=list(range(rows)))
        for _ in range(n_groups)
    ]


@pytest.mark.parametrize("fold", [True, False], ids=["fold", "nofold"])
@pytest.mark.parametrize("dtype", ["float64", "float32"])
def test_stream_priced_bitwise_equals_sync(fold, dtype):
    """stream_priced == price_job == step_times for random placements,
    with folding on and off and in both precisions — the async path must
    run the same programs, so equality is exact, not approximate."""
    app = apps.get("summa")
    n = app.default_procs
    eng = to_jax(batch_simulator(
        app.collective, _spec(app, n), app.tile_grid(n),
        step_flops=float(app.step_flops(n))), dtype=dtype)
    rng = np.random.default_rng(7)
    jobs = _stack_jobs(eng, rng)
    streamed = {id(j): t for j, t in
                stream_priced(iter(jobs), fold=fold, incremental=fold)}
    for job in jobs:
        sync = price_job(job, fold=fold, incremental=fold)
        direct = np.asarray(job.engine.step_times(job.stack, fold=fold,
                                                  incremental=fold))
        assert np.array_equal(streamed[id(job)], sync)
        assert np.array_equal(sync, direct)


def test_stream_priced_matches_price_stacks_numpy():
    """The NumPy engine's streamed groups equal the packed-sweep values
    bit for bit (independent buckets: packing never changed the
    arithmetic)."""
    app = apps.get("summa")
    n = app.default_procs
    eng = batch_simulator(app.collective, _spec(app, n), app.tile_grid(n),
                          step_flops=float(app.step_flops(n)))
    rng = np.random.default_rng(11)
    jobs = _stack_jobs(eng, rng)
    packed = price_stacks([(j.engine, j.stack) for j in jobs])
    streamed = {id(j): t for j, t in stream_priced(iter(jobs))}
    for job, expect in zip(jobs, packed):
        assert np.array_equal(streamed[id(job)], expect)


def _spec(app, n):
    from repro.sim.cost import spec_for

    return spec_for(tuple(int(s) for s in app.machine_shape(n)))


# --------------------------------------------------------------- mechanics
def test_bounded_queue_limits_producer_lead():
    """The producer blocks once queue_size groups wait unconsumed: its
    lead over the consumer stays <= queue_size + in_flight + 1 (one
    group in its hands, in_flight dispatched, queue_size buffered)."""
    app = apps.get("summa")
    n = app.default_procs
    eng = batch_simulator(app.collective, _spec(app, n), app.tile_grid(n),
                          step_flops=float(app.step_flops(n)))
    rng = np.random.default_rng(3)
    produced = []
    consumed = []
    max_lead = []
    queue_size, in_flight = 2, 1

    def jobs():
        for job in _stack_jobs(eng, rng, n_groups=12, rows=2):
            produced.append(1)
            yield job

    for _job, _t in stream_priced(jobs(), queue_size=queue_size,
                                  in_flight=in_flight):
        time.sleep(0.02)          # slow consumer: let the producer run
        consumed.append(1)
        max_lead.append(len(produced) - len(consumed))
    assert len(consumed) == 12
    assert max(max_lead) <= queue_size + in_flight + 1


def test_producer_exception_propagates():
    app = apps.get("summa")
    n = app.default_procs
    eng = batch_simulator(app.collective, _spec(app, n), app.tile_grid(n),
                          step_flops=float(app.step_flops(n)))
    rng = np.random.default_rng(5)

    def jobs():
        yield _stack_jobs(eng, rng, n_groups=1)[0]
        raise RuntimeError("expansion exploded")

    results = []
    with pytest.raises(RuntimeError, match="expansion exploded"):
        for job, t in stream_priced(jobs()):
            results.append(t)
    # The group produced before the failure still priced.
    assert len(results) <= 1


def test_early_consumer_exit_unwinds_producer():
    """Closing the result generator mid-stream must stop the producer
    thread (no daemon thread left spinning on a full queue)."""
    app = apps.get("summa")
    n = app.default_procs
    eng = batch_simulator(app.collective, _spec(app, n), app.tile_grid(n),
                          step_flops=float(app.step_flops(n)))
    rng = np.random.default_rng(9)
    before = threading.active_count()
    gen = stream_priced(iter(_stack_jobs(eng, rng, n_groups=8)),
                        queue_size=1, in_flight=1)
    next(gen)
    gen.close()
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


def test_invalid_bounds_rejected():
    with pytest.raises(ValueError):
        list(stream_priced(iter([]), queue_size=0))
    with pytest.raises(ValueError):
        list(stream_priced(iter([]), in_flight=0))
