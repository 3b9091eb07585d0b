"""A configuration that is not Cannon's, and its cell, added as new files
and entries only: a throwaway program ``tanh(x @ w)`` that counts its calls
(``toy.calls``) and runs under a named scope (``toy_layer``), with its own
config (``cpu_sizes`` included), driver, reference, planted faults and two
metric readers, in an overlay laid out like the repository
(``bench_helpers``). The committed tiny machinery runs it sound, with the
control and with each fault; a traced run reads the counter, and the scope
is read back from a trace of the same cell recorded on one v5e chip
(``harness.run(..., traced=True, keep=...)`` at its ``cpu_sizes``, a
0.05 s window, written as ``write_throwaway`` writes it). No committed file
of the benchmark changes."""
import collections
import json
import textwrap
from pathlib import Path

import pytest

from bench_helpers import (ROOT, cell_faults, read_spec, run_python,
                           write_tiny_bench)
from bench import run as harness
from bench import trace as T

CELL = "toy-tanh.x1"
RECORDED = ROOT / "tests/bench/data/toy-tanh.x1.xplane.pb"

FILES = {
    "src/toy_program.py": '''
        """The throwaway program: one layer, tanh(x @ w)."""
        import jax
        import jax.numpy as jnp

        from repro.runtime import tracing


        @jax.jit
        def layer(x, w):
            with jax.named_scope("toy_layer"):
                return jnp.tanh(jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST))


        def step(x, w):
            tracing.count("toy.calls")
            return layer(x, w)
    ''',
    "bench/configs/toy-tanh.json": json.dumps({
        "driver": "toy", "n": 4096, "check": {"max_err": 1e-4},
        "cpu_sizes": {"n": 64}}),
    "bench/references/toy.py": '''
        """Plain reference of the throwaway program, in float64 on the host;
        the control rounds the operands to bfloat16."""
        import jax
        import jax.numpy as jnp
        import numpy as np


        def operands(seed, n):
            kx, kw = jax.random.split(jax.random.key(seed))
            return (jax.random.normal(kx, (n, n), jnp.float32),
                    jax.random.normal(kw, (n, n), jnp.float32) / np.sqrt(n))


        def control(x, w):
            return jnp.tanh(jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                                    preferred_element_type=jnp.float32))


        def max_err(out, seed, n):
            x, w = (np.asarray(a, np.float64) for a in operands(seed, n))
            return float(np.max(np.abs(np.asarray(out, np.float64) - np.tanh(x @ w))))
    ''',
    "bench/drivers/toy.py": '''
        """Driver of the throwaway program."""
        import importlib.util
        from pathlib import Path

        import jax

        import toy_program

        _spec = importlib.util.spec_from_file_location(
            "toy_reference", Path(__file__).parents[1] / "references" / "toy.py")
        reference = importlib.util.module_from_spec(_spec)
        _spec.loader.exec_module(reference)


        class Driver:
            steps_per_call = 1

            def __init__(self, config, seed, devices):
                self.config, self.seed, self.n = config, seed, config["n"]
                with jax.default_device(devices[0]):
                    self.x, self.w = reference.operands(seed, self.n)

            def call(self):
                return toy_program.step(self.x, self.w)

            def work(self):
                return {"flops": 2 * self.n ** 3, "hbm_bytes": 12 * self.n ** 2}

            def check(self, outputs):
                err = max(reference.max_err(o, self.seed, self.n) for o in outputs)
                outputs.clear()
                return [{"name": "max_err", "value": err,
                         "limit": self.config["check"]["max_err"]}]

            def control(self):
                out = reference.control(*reference.operands(self.seed, self.n))
                return {"max_err": reference.max_err(out, self.seed, self.n)}
    ''',
    "tests/bench/faults/toy.py": '''
        """Planted faults of the throwaway driver."""
        import importlib.util
        from pathlib import Path
        from unittest import mock

        FAULTS = {"unchanged": (1, 4), "altered": (1, 4)}


        def patches(kind):
            import toy_program

            spec = importlib.util.spec_from_file_location(
                "toy_reference", Path(__file__).parents[3] / "bench/references/toy.py")
            reference = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(reference)
            step = toy_program.step
            return {
                "control": [mock.patch.object(toy_program, "step", reference.control)],
                "unchanged": [mock.patch.object(toy_program, "layer", lambda x, w: x)],
                "altered": [mock.patch.object(
                    toy_program, "step", lambda x, w: step(x, w).at[1, 2].add(1.0))],
            }[kind]
    ''',
    "bench/metrics/toy_calls_per_step.py": '''
        def read(ctx):
            calls = ctx.counters["toy.calls"]
            return calls / ctx.steps if calls else None
    ''',
    "bench/metrics/toy_layer_ms.py": '''
        def read(ctx):
            t_ns = ctx.scoped_op_ns(lambda scopes: "toy_layer" in scopes)
            return t_ns / ctx.steps * 1e-6 if t_ns else None
    ''',
}


def write_throwaway(overlay: Path) -> None:
    """The committed spec with the throwaway configuration's entries, and
    its files, under ``overlay``."""
    for rel, text in FILES.items():
        (overlay / rel).parent.mkdir(parents=True, exist_ok=True)
        (overlay / rel).write_text(textwrap.dedent(text).lstrip())
    spec = read_spec()
    spec["configs"].append({"name": "toy-tanh", "source": "test",
                            "file": "bench/configs/toy-tanh.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "toy-tanh",
                              "traffic": "closed1.p1", "chips": 1, "why": "test"})
    for m in spec["per_layer"]:
        if m["name"] in ("dispatch_ms", "mfu", "device_idle.step"):
            m["workloads"].append(CELL)
    spec["per_layer"] += [
        {"name": name, "unit": unit, "better": "lower", "source": source,
         "layer": "toy", "moves": "step_ms", "workloads": [CELL]}
        for name, unit, source in [("toy_calls_per_step", "calls", "program_counter"),
                                   ("toy_layer_ms", "ms", "device_trace")]]
    (overlay / "BENCHMARK.json").write_text(json.dumps(spec))


def committed() -> dict:
    return {p: p.read_bytes() for d in ("bench", "tests/bench")
            for p in (ROOT / d).rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("added")
    before = committed()
    write_throwaway(tmp / "overlay")
    spec = write_tiny_bench(tmp / "tiny", tmp / "overlay")
    faults = [f for f, c in cell_faults(tmp / "overlay") if c == CELL]
    scenarios = [f"{k}:{CELL}" for k in ("sound", "readings", "control", "traced", *faults)]
    proc = run_python(["tests/bench/run_tiny.py", str(spec), "--overlay",
                       str(tmp / "overlay"), *scenarios], tmp, devices=1)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return {"rows": {r["scenario"]: r for r in rows}, "faults": faults,
            "overlay": tmp / "overlay", "unchanged": committed() == before}


def test_added_configuration_is_correct_and_its_faults_are_not(added):
    rows = added["rows"]
    assert added["faults"] == ["unchanged", "altered"]
    assert rows["sound"]["correct"] and rows["traced"]["correct"], rows["sound"]["check"]
    assert rows["sound"]["work"] == {"flops": 2 * 64 ** 3, "hbm_bytes": 12 * 64 ** 2}
    r = rows["readings"]
    assert any(r["readings"][k] > r["limits"][k] for k in r["limits"]), r
    for kind in ["control", *added["faults"]]:
        assert not rows[kind]["correct"], (kind, rows[kind]["check"])
    assert added["unchanged"]


def test_added_metric_reads_the_programs_counter(added):
    """The counter is read from a traced run on the CPU; the scope's reader
    finds no op on a chip there, so its metric is left out, not read as 0."""
    traced = added["rows"]["traced"]
    assert traced["counter.toy.calls"] == traced["calls"] >= 1
    assert traced["metrics"]["toy_calls_per_step"]["value"] == 1.0
    assert {"dispatch_ms", "mfu", "device_idle.step"} <= set(traced["metrics"])
    assert "toy_layer_ms" not in traced["metrics"]


def test_added_metric_reads_the_programs_scope(added):
    """Read back from the cell's trace recorded on one v5e chip, as the
    harness hands it to the reader: the one leaf op under ``toy_layer`` is
    the fused product and tanh, 70334 ns over the window's 84 calls, which
    the chip run printed as 0.000837 ms; the copies XLA inserts carry no
    scope."""
    b = harness.Bench(added["overlay"] / "BENCHMARK.json",
                      [added["overlay"] / "bench", ROOT / "bench"])
    trace = T.load(RECORDED)
    summary = T.summarize(trace, [0])
    tf_op = T.read_tf_ops(RECORDED)
    steps = sum(s.name == "dispatch" for s in summary.spans)
    ctx = harness.Context(
        summary=summary, steps=steps, window_s=summary.window_ns * 1e-9, chips=1,
        work={}, peaks={}, dispatch_s=0.0, counters=collections.Counter(),
        spans=trace.program, tf_op=tf_op)
    scoped = {T.instruction_name(text) for text, op in tf_op.items()
              if "toy_layer" in T.scopes(op)}
    assert scoped == {"convolution_tanh_fusion"} and steps == 84
    value = b.module("metrics", "toy_layer_ms").read(ctx)
    assert value == pytest.approx(70334 / 84 * 1e-6)
    assert b.module("metrics", "toy_calls_per_step").read(ctx) is None
