"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``. Everything else
is found by name: the configuration in the file its ``configs`` entry names,
the traffic mix in ``bench/traffic/<traffic>.json``, the driver of the
configuration's program in ``bench/drivers/<driver>.py``, and each per-layer
metric in ``bench/metrics/<metric>.py``. A cell, configuration, traffic mix
or metric is added by adding files and entries; nothing here changes.

A run: make the inputs from the seed and build the program's mesh, call the
entry once (compiling, or loading from JAX's persistent compilation cache in
``<checkout>/.bench_jax_cache``), then call it in a closed loop for ``--seconds``.
With ``--trace 1`` the loop runs under the profiler and the per-layer
metrics are read from its trace and from the program's counters; otherwise
the end-to-end metrics are reported. After the loop the outputs of two
calls (the last, and one drawn from the seed among the first few) are
compared with the configuration's plain reference.

Standard output ends with the window's counts (backend compilations, calls,
set-up phases and, traced, the program's counters), then one JSON line:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, (traced)
``breakdown``, and last ``check``, each number compared beside its limit.
Standard error ends with the same check. Without an accelerator, with fewer
chips than the cell asks for, or on a device missing from
``bench/peaks.json``, the run exits non-zero and prints no result.

Adding a configuration and its cell
-----------------------------------
A configuration brings, as new files:

- its config file (``configs`` entry ``file``), holding ``driver``, the
  ``check`` limits of ``correct``, and ``cpu_sizes``: the keys the CPU
  tests override to run it in seconds (``tests/bench/bench_helpers.py``).
  A run here never reads ``cpu_sizes``.
- its driver, ``bench/drivers/<driver>.py``: a class ``Driver(config,
  seed, devices)`` with
  - ``steps_per_call``: steps one call makes;
  - ``call()``: one call of the program's entry, returning what the check
    compares. It is held while later calls run, so it must not be a buffer
    that the next call donates: a train driver returns its loss and logits,
    not its state;
  - ``work()``: counts per step from shapes; ``flops`` and ``hbm_bytes`` at
    least (``mfu`` reads them);
  - ``check(kept)``: compare the kept outputs with the reference, freeing
    the program's state first; a list of ``{"name", "value", "limit"}``;
  - ``control()``: the same numbers, read off the control (the reference
    one precision below the configuration's) in the program's place.
- its plain reference, beside the driver under ``bench/references/``,
  importing nothing of the program;
- its planted faults, ``tests/bench/faults/<driver>.py``: ``patches(kind)``
  for ``control`` and each fault, and ``FAULTS``, each fault's chip counts;
- readers of its own per-layer metrics, ``bench/metrics/<metric>.py``: a
  function ``read(ctx)`` of a ``Context`` that returns a number, or None
  where the window holds nothing to read (never 0 for a missing scope,
  span or counter).

A cell is a ``workloads`` entry over a traffic mix; it adds its name to the
``workloads`` lists of ``dispatch_ms``, ``mfu`` and ``device_idle.step``,
which every cell reports.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from the start of the process

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache of the benchmark's runs: inside the
#: checkout, at a fixed path, and apart from any cache that other commands
#: run in the checkout have filled.
CACHE_DIR = ROOT / ".bench_jax_cache"


class NoDevice(RuntimeError):
    """The machine lacks what the cell needs; the run prints no result."""


# ------------------------------------------------------------------- lookup
class Bench:
    """``BENCHMARK.json`` and the files it names.

    ``dirs`` are searched in order for ``traffic/``, ``drivers/`` and
    ``metrics/``; configuration files are named relative to the spec.
    """

    def __init__(self, spec_path: Path, dirs: list[Path]):
        self.spec_path = spec_path
        self.spec = json.loads(spec_path.read_text())
        self.dirs = dirs
        with open(BENCH / "peaks.json") as f:
            self.peaks = json.load(f)

    def _entry(self, section: str, name: str) -> dict:
        for e in self.spec[section]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {section} entry named {name!r} in {self.spec_path}")

    def _file(self, kind: str, name: str, suffix: str) -> Path:
        for d in self.dirs:
            p = d / kind / f"{name}{suffix}"
            if p.is_file():
                return p
        raise FileNotFoundError(f"no {kind}/{name}{suffix} under {self.dirs}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        path = self.spec_path.parent / self._entry("configs", name)["file"]
        return json.loads(path.read_text())

    def traffic(self, name: str) -> dict:
        return json.loads(self._file("traffic", name, ".json").read_text())

    def module(self, kind: str, name: str):
        path = self._file(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics(self, section: str, cell: str) -> list[dict]:
        """The ``section`` metrics this cell reports."""
        return [m for m in self.spec[section]
                if cell in m.get("workloads", [cell])]


# ------------------------------------------------------------------ devices
def accelerators(chips: int, peaks: dict) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoDevice("JAX finds no accelerator")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX has {len(devices)}")
    if devices[0].device_kind not in peaks:
        raise NoDevice(f"{devices[0].device_kind!r} is not in bench/peaks.json")
    return devices


class CompileCounter:
    """Backend compilations while ``active``: each program handed to the
    backend that the persistent compilation cache did not answer."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax._src import monitoring

        self.requests = self.hits = 0
        self.active = False
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, _secs, **_):
        if self.active and name == self.REQUEST:
            self.requests += 1

    def _event(self, name, **_):
        if self.active and name == self.HIT:
            self.hits += 1

    @property
    def compiles(self) -> int:
        return self.requests - self.hits


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader reads: the traced window, reduced."""

    summary: object            # trace.Summary of the traced window
    steps: int                 # steps completed in it
    window_s: float            # its length on the trace's clock
    chips: int                 # chips the program runs on
    work: dict                 # the driver's counts per step
    peaks: dict                # this device's row of bench/peaks.json
    dispatch_s: float          # host time in the entry's calls
    counters: collections.Counter  # the program's counts in the window
    spans: list                # the program's host spans (trace.Span)
    tf_op: dict                # HLO text -> the op's tf_op

    def span_ns(self, name: str) -> float:
        """Host time in the program's spans ``repro.<name>`` inside the
        window (0 where it has none)."""
        from bench import trace as tracemod

        return tracemod.span_ns(self.spans, name, self.summary.window)

    def scoped_op_ns(self, pick) -> float:
        """Device leaf-op time, mean over chips, of the ops whose named
        scopes (``trace.scopes`` of their ``tf_op``) ``pick`` accepts (0
        where none does)."""
        from bench import trace as tracemod

        return tracemod.scoped_op_ns(self.summary, self.tf_op, pick)


# --------------------------------------------------------------------- run
def run(bench: Bench, cell_name: str, seed: int, seconds: float, traced: bool,
        devices=None, keep: Path | None = None) -> tuple[dict, dict]:
    """One run of a cell: the result line as a dict, and the window's counts
    of calls and backend compilations with the set-up's phases and, traced,
    the program's counters (``counter.<name>``) and the device's idle time
    by the harness span the host was in, in seconds.

    ``devices`` skips the look for accelerators (and the device's peaks,
    which are then those of the first row of the table): tests drive the
    rest of a run on the CPU this way. ``keep``, traced, is where the raw
    trace (``.xplane.pb``) is copied before its directory goes.
    """
    import jax

    from bench import trace as tracemod
    from repro.runtime import tracing
    from repro.runtime.compile_cache import enable_compile_cache

    notes = {"import_s": time.perf_counter() - T0}
    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    if (traffic["loop"], traffic["callers"]) != ("closed", 1):
        raise ValueError("the harness drives a closed loop with one caller")
    if traffic["procs"] != cell["chips"]:
        raise ValueError(f"{traffic['procs']} processes on {cell['chips']} chips")
    if devices is None:
        devices = accelerators(cell["chips"], bench.peaks)
        peaks = bench.peaks[devices[0].device_kind]
    else:
        peaks = next(iter(bench.peaks.values()))
    used = devices[:traffic["procs"]]
    enable_compile_cache()
    notes["devices_s"] = time.perf_counter() - T0 - notes["import_s"]

    t = time.perf_counter()
    drv = bench.module("drivers", config["driver"]).Driver(config, seed, used)
    notes["inputs_s"] = time.perf_counter() - t
    t = time.perf_counter()
    jax.block_until_ready(drv.call())
    notes["warmup_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - T0

    checked_at = random.Random(seed).randrange(traffic["checked_calls"])
    kept = []
    counter = CompileCounter()
    tmp = tempfile.TemporaryDirectory() if traced else None
    if traced:
        before = tracing.counters()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
        span = jax.profiler.TraceAnnotation
    else:
        span = lambda _name: contextlib.nullcontext()  # noqa: E731
    dispatch_s, calls = 0.0, 0
    counter.active = True
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        t0 = time.perf_counter()
        with span("bench.dispatch"):
            out = drv.call()
        t1 = time.perf_counter()
        with span("bench.block"):
            jax.block_until_ready(out)
        t2 = time.perf_counter()
        dispatch_s += t1 - t0
        if calls == checked_at:
            kept.append(out)
        calls += 1
        if t2 >= deadline:
            break
    window_s = t2 - start
    counter.active = False
    if traced:
        jax.profiler.stop_trace()
        counted = tracing.counters() - before
    if calls - 1 != checked_at:
        kept.append(out)
    del out
    steps = calls * drv.steps_per_call
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)

    result = {"correct": False, "attempted": steps, "failed": 0}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if traced:
        xplane = tracemod.find_xplane(tmp.name)
        if keep is not None:
            keep.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, keep)
        trace = tracemod.load(xplane)
        tf_op = tracemod.read_tf_ops(xplane)
        tmp.cleanup()
        summary = tracemod.summarize(trace, [d.id for d in used])
        ctx = Context(summary=summary, steps=steps,
                      window_s=summary.window_ns * 1e-9, chips=len(used),
                      work=drv.work(), peaks=peaks, dispatch_s=dispatch_s,
                      counters=counted, spans=trace.program, tf_op=tf_op)
        metrics = {}
        for m in bench.metrics("per_layer", cell_name):
            value = bench.module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.mean("busy_ns") * 1e-9
        device["window_s"] = ctx.window_s
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": summary.longest_gaps(10)}
        notes.update({f"idle_s_in_{name}": ns * 1e-9
                       for name, ns in summary.gap_ns_by_span().items()})
        notes.update({f"counter.{name}": n for name, n in sorted(counted.items())})
    else:
        e2e = {"step_ms": window_s / steps * 1e3, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in bench.metrics("end_to_end", cell_name)}

    checks = drv.check(kept)
    result.update(correct=all(c["value"] <= c["limit"] for c in checks),
                  metrics=metrics, device=device)
    if traced:
        result["breakdown"] = breakdown
    result["check"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in checks}
    return result, {"compiles_in_window": counter.compiles, "calls": calls,
                    **{k: round(v, 3) for k, v in notes.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The program's enable_compile_cache() takes the cache directory from
    # here, whatever the environment named.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # Import this checkout's code, and never bench/trace.py as "trace".
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]
    bench = Bench(ROOT / "BENCHMARK.json", [BENCH])
    try:
        result, counts = run(bench, args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except NoDevice as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    print(" ".join(f"{k}={v}" for k, v in counts.items()))
    print(json.dumps(result))
    for name, c in result["check"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
