"""The program's own spans, counters and named scopes
(``repro.runtime.tracing``, the matmul entry and its cache of programs, the
Cannon body).

The multi-device checks run in one child process on 8 CPU devices, as in
``test_distributed.py``; each test reads its part of the child's report.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
ALGORITHMS = ["cannon", "summa", "pumma", "johnson", "solomonik", "cosma"]

CHILD = r"""
import collections, json, re
from unittest import mock
import jax, numpy as np
from jax._src import monitoring
from jax.sharding import PartitionSpec as P
from repro.core import Machine, GPU
from repro.core.commvolume import MatmulProblem
from repro.matmul import (cannon, summa, pumma, johnson, solomonik, cosma,
                          runtime_heuristic_mapper)
from repro.matmul.common import build_grid, make_inputs, reference_matmul
from repro.runtime import tracing


def plain_wrapper(grid, body_factory, body_args, in_specs, out_spec):
    # The parent's entry: a new jax.jit of the same shard_map on every call.
    return jax.jit(jax.shard_map(body_factory(*body_args), mesh=grid.mesh,
                                 in_specs=in_specs, out_specs=out_spec,
                                 check_vma=False))


def counted(fn):
    # fn's result and the builds and calls the entry counted in it.
    before = tracing.counters()
    out = np.asarray(fn())
    got = tracing.counters() - before
    return out, got["matmul.builds"], got["matmul.calls"]


jax.config.update("jax_enable_compilation_cache", False)  # every compile counts
a, b = make_inputs(16, 24, 32, seed=1)
ref = reference_matmul(a, b)
m4 = Machine(GPU, shape=(2, 2))
devs4 = jax.devices()[:4]
grids = {
    "cannon": cannon.grid_for(m4, devs4),
    "summa": summa.grid_for(m4, devs4),
    "pumma": pumma.grid_for(m4, devs4),
    "johnson": johnson.grid_for(Machine(GPU, shape=(8, 1))),
    "solomonik": solomonik.grid_for(Machine(GPU, shape=(2, 4)), c=2),
    "cosma": cosma.grid_for(Machine(GPU, shape=(8, 1)), MatmulProblem(16, 32, 24)),
}
# JAX's own events: each lowering to StableHLO and each backend compile.
WORK = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration")
events = collections.Counter()
monitoring.register_event_duration_secs_listener(
    lambda name, _secs, **_: events.update([name] if name in WORK else []))

report = {}
for name, grid in grids.items():
    mod = globals()[name]
    _, first_builds, _ = counted(lambda: mod.matmul(a, b, grid))
    before = tracing.counters()
    events.clear()
    spanned = [np.asarray(mod.matmul(a, b, grid)) for _ in range(2)]
    spanned_events = dict(events)
    got = tracing.counters() - before
    with mock.patch.object(mod, "sharded_matmul_wrapper", plain_wrapper):
        events.clear()
        plain = [np.asarray(mod.matmul(a, b, grid)) for _ in range(2)]
        plain_events = dict(events)
    report[name] = {"builds": [first_builds, got["matmul.builds"]],
                    "calls": got["matmul.calls"],
                    "events": [spanned_events, plain_events],
                    "equal": all(np.array_equal(s, p) for s, p in zip(spanned, plain))}

# The program's key, case by case on new grids: the builds each call
# counted, and its error against the host product.
hb2d = cannon.grid_for(m4, devs4)
heuristic = build_grid(runtime_heuristic_mapper(m4), (2, 2), cannon.AXES, devs4)
cases = {
    "hierarchical_block2D": lambda: cannon.matmul(a, b, hb2d),
    "runtime_heuristic_order": lambda: cannon.matmul(a, b, heuristic),
    "use_kernel": lambda: cannon.matmul(a, b, hb2d, use_kernel=True),
    "same_grid_and_body_again": lambda: cannon.matmul(a, b, hb2d),
    "equal_grid_built_again": lambda: cannon.matmul(a, b, cannon.grid_for(m4, devs4)),
    "solomonik_c1": lambda: solomonik.matmul(
        a, b, solomonik.grid_for(Machine(GPU, shape=(2, 2)), c=1, devices=devs4)),
    "solomonik_c2": lambda: solomonik.matmul(
        a, b, solomonik.grid_for(Machine(GPU, shape=(2, 4)), c=2)),
}
report["key"] = {}
for case, fn in cases.items():
    out, builds, calls = counted(fn)
    report["key"][case] = {"builds": builds, "calls": calls,
                           "err": float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))}
report["programs"] = {"hierarchical_block2D": len(hb2d.programs),
                      "runtime_heuristic_order": len(heuristic.programs)}
report["same_shape_other_order"] = (heuristic.shape == hb2d.shape
                                    and heuristic.mesh != hb2d.mesh)

q = 2
fn = plain_wrapper(grids["cannon"], cannon.cannon_body, (q, False), (P("x", "y"),) * 2,
                   P("x", "y"))
text = fn.lower(a, b).compile().as_text()
report["op_names"] = sorted(set(re.findall(r'op_name="([^"]+)"', text)))
print("REPORT " + json.dumps(report))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                          text=True, timeout=420, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("REPORT "))
    return json.loads(line[len("REPORT "):])


def test_span_is_a_trace_annotation_that_runs_without_a_profiler():
    import jax

    from repro.runtime import tracing

    s = tracing.span("matmul.call")
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass


def test_counters_count_and_return_a_copy():
    from repro.runtime import tracing

    before = tracing.counters()
    tracing.count("test.things")
    tracing.count("test.things", 2)
    got = tracing.counters()
    assert (got - before)["test.things"] == 3
    got["test.things"] += 100
    assert (tracing.counters() - before)["test.things"] == 3


@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_entry_matches_a_plain_jit_of_the_same_body(report, algorithm):
    assert report[algorithm]["equal"]


@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_each_call_counts_one_build(report, algorithm):
    """A first call builds the program once; two further calls on the same
    grid build nothing, and each counts one call."""
    assert report[algorithm]["builds"] == [1, 0]
    assert report[algorithm]["calls"] == 2


@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_entry_does_a_plain_jits_work(report, algorithm):
    """Further calls reuse the program: no lowering and no backend compile,
    where a plain jit built anew on each call does one of each per call."""
    spanned, plain = report[algorithm]["events"]
    assert spanned == {}
    assert plain == {
        "/jax/core/compile/jaxpr_to_mlir_module_duration": 2,
        "/jax/core/compile/backend_compile_duration": 2}


@pytest.mark.parametrize("case,builds", [
    ("hierarchical_block2D", 1),
    ("runtime_heuristic_order", 1),   # same grid shape, another device order
    ("use_kernel", 1),                # same grid, the Pallas local product
    ("same_grid_and_body_again", 0),
    ("equal_grid_built_again", 1),    # a grid keeps its own programs
    ("solomonik_c1", 1),
    ("solomonik_c2", 1),
])
def test_program_key(report, case, builds):
    got = report["key"][case]
    assert (got["builds"], got["calls"]) == (builds, 1)
    assert got["err"] < 1e-5


def test_grids_of_two_mappers_keep_programs_apart(report):
    assert report["same_shape_other_order"]
    assert report["programs"] == {"hierarchical_block2D": 2,
                                  "runtime_heuristic_order": 1}


@pytest.mark.parametrize("scope,op", [
    ("skew", "jit(_where)/select_n"),   # the skew's predicated copy
    ("skew", "shift/ppermute"),         # the skew's single-step shifts
    ("local_matmul", "dot_general"),
    ("local_matmul", "add"),            # the accumulate beside the dot
])
def test_named_scopes_reach_op_names_through_the_loops(report, scope, op):
    assert any(f"/{scope}/" in n and n.endswith(op) for n in report["op_names"]), \
        report["op_names"]


def test_every_ppermute_is_under_shift(report):
    permutes = [n for n in report["op_names"] if n.endswith("ppermute")]
    assert permutes and all("/shift/" in n for n in permutes), permutes
