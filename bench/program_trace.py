"""The program's own instrumentation in a profiler trace, and a traced run of
a cell that reads it.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s> \\
        --keep <path.xplane.pb> [--spec <BENCHMARK.json>]

The program (``repro.runtime.tracing``) puts two things in a trace:

- host spans ``repro.<name>``, on the same clock as the device's ops: the
  matmul entry's ``repro.matmul.build`` (the ``shard_map`` and ``jax.jit``,
  called at once), and a counter ``matmul.builds`` bumped once per build;
- named scopes on the Cannon body's device work (``skew``, ``shift``,
  ``local_matmul``), which reach each op's ``tf_op`` stat:
  ``jit(body)/shard_map/skew/while/body/closed_call/shift/ppermute:``.

``bench/trace.py`` reduces a trace to the harness's spans and device-op
intervals and keeps neither: ``jax.profiler.ProfileData`` gives an event's
name and timing stats but not its metadata's. This module reads the spans,
cuts each call of the entry into phases at JAX's own events, reads the
``tf_op`` of every op, and from them, per step of a window:

- ``entry_<phase>_ms``: host time in each phase of the entry's calls
  (``trace`` holds the build too);
- ``entry_builds_per_step``: programs the entry built per step;
- ``skew_ms``: device leaf-op time under ``skew``, mean over chips;
- ``unscoped_ms``: device leaf-op time under none of the scopes;

and the device's idle time by the innermost program span the host was in.

The command runs the cell once as ``bench/run.py --workload <cell> --trace 1``
does, through its ``run``, writes the trace to ``--keep``, and prints the
harness's counts and result line, then one JSON line of these readings, the
window's time per step and the leaf ops by time with their ``tf_op``. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import functools
import itertools
import json
import os
import shutil
import sys
from collections import defaultdict
from pathlib import Path
from unittest import mock

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":
    # Import this checkout's code, and never bench/trace.py as "trace".
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]

from bench import trace as T  # noqa: E402

PREFIX = "repro."
#: The program's named scopes on the device's work (``jax.named_scope``).
SCOPES = frozenset({"skew", "shift", "local_matmul"})
PHASES = ("trace", "lower", "load", "launch")
#: JAX's own host events: a jit call (``PjitFunction(<name>)``), and in it
#: the call's lowering to StableHLO and its executable's run. They are no
#: stable interface of JAX: ``readings`` fails where the calls lost them.
CALL, LOWER, EXECUTE = "PjitFunction(", "lower_sharding_computation", "ExecuteReplicated.__call__"


def load_spans(path: str | Path) -> list[T.Span]:
    """The program's host spans, without their ``repro.`` prefix, and the
    ``phases`` of the entry's calls, ordered by start and, at one start,
    longest first."""
    from jax.profiler import ProfileData

    spans, jax_events = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(T.Span(e.name[len(PREFIX):], e.start_ns, e.end_ns))
                elif e.name.startswith(CALL) or e.name in (LOWER, EXECUTE):
                    jax_events.append(T.Span(e.name, e.start_ns, e.end_ns))
    return sorted(spans + phases(spans, jax_events), key=lambda s: (s.start, -s.end))


def phases(spans: list[T.Span], jax_events: list[T.Span]) -> list[T.Span]:
    """Each call of the entry cut at JAX's events in it into four spans.
    The call is the first jit call to start once a ``matmul.build`` span
    has ended (the longest of those that start together), since the entry
    calls what it built at once. ``matmul.trace`` runs up to the lowering,
    ``matmul.lower`` is the lowering, ``matmul.load`` runs from there to the
    executable's run (the persistent-cache read and the load onto each
    device, which JAX leaves unannotated, or a backend compile on a miss)
    and ``matmul.launch`` from the run to the call's end. A call without a
    lowering and a run in it gets none."""
    calls = sorted((e for e in jax_events if e.name.startswith(CALL)),
                   key=lambda e: (e.start, -e.end))
    starts = [c.start for c in calls]
    out = []
    for build in (s for s in spans if s.name == "matmul.build"):
        i = bisect.bisect_left(starts, build.end)
        if i == len(calls):
            continue
        call = calls[i]
        inside = [e for e in jax_events if call.start <= e.start and e.end <= call.end]
        lower = [e for e in inside if e.name == LOWER]
        run = [e.start for e in inside if e.name == EXECUTE]
        if not lower or not run:
            continue
        cuts = (call.start, min(e.start for e in lower),
                max(e.end for e in lower), max(run), call.end)
        out += [T.Span(f"matmul.{phase}", lo, hi)
                for phase, lo, hi in zip(PHASES, cuts, cuts[1:])]
    return out


# ------------------------------------------------------------- op metadata
# The raw XSpace protobuf (tsl/profiler/protobuf/xplane.proto), read for the
# fields needed here: XSpace.planes = 1; XPlane.name = 2, lines = 3
# (skipped), event_metadata = 4, stat_metadata = 5 (maps: key = 1, value =
# 2); XEventMetadata.name = 2, stats = 5; XStatMetadata.id = 1, name = 2;
# XStat.metadata_id = 1, str_value = 5, ref_value = 7 (the id of the stat
# metadata whose name is the string).
def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a serialized message: an int
    for a varint, a memoryview for a length-delimited or fixed-width one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire in (1, 2, 5):
            if wire == 2:
                n, i = _varint(buf, i)
            else:
                n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def read_tf_ops(path: str | Path) -> dict[str, str]:
    """HLO text -> ``tf_op`` of every event the device planes name: "" where
    the metadata carries none, as for copies and conversions XLA inserts. An
    HLO text that carries two different ``tf_op``s is an error."""
    out: dict[str, str] = {}
    for f, plane in _fields(memoryview(Path(path).read_bytes())):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(_map_value(v))
            elif g == 5:
                stat = dict(_fields(_map_value(v)))
                stat_names[stat.get(1, 0)] = bytes(stat.get(2, b"")).decode()
        if not T.DEVICE_PLANE.match(name):
            continue
        tf_op_ids = {i for i, n in stat_names.items() if n == "tf_op"}
        for event in events:
            text, tf_op = "", ""
            for g, v in _fields(event):
                if g == 2:
                    text = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1, 0) in tf_op_ids:
                        tf_op = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names[stat.get(7, 0)])
            seen = out.setdefault(text, tf_op)
            if seen and tf_op and seen != tf_op:
                raise ValueError(f"{text!r} carries two tf_ops: "
                                 f"{seen!r} and {tf_op!r}")
            out[text] = seen or tf_op
    return out


def scopes(tf_op: str) -> set[str]:
    """The components of a ``tf_op`` path: the named scopes the op ran
    under, among the names of the transforms and control flow."""
    return set(tf_op.rsplit(":", 1)[0].split("/")) if tf_op else set()


# ----------------------------------------------------------------- reduction
@dataclasses.dataclass
class ProgramTrace:
    summary: T.Summary                # the harness's reduction of the trace
    spans: list[T.Span]               # the program's, as ``load_spans`` orders them
    tf_op: dict[str, str]             # HLO text -> tf_op

    def span_ns(self, name: str) -> float:
        """Host time in the program's spans called ``name``, inside the
        window."""
        lo, hi = self.summary.window
        return sum(max(0.0, min(s.end, hi) - max(s.start, lo))
                   for s in self.spans if s.name == name)

    def span_at(self, t: float) -> str | None:
        """The innermost program span the host was in at ``t`` (None if
        none): of the spans that hold ``t``, the one that started last."""
        i = bisect.bisect_right(self._starts, t) - 1
        while i >= 0 and self._reach[i] > t:
            if t < self.spans[i].end:
                return self.spans[i].name
            i -= 1
        return None

    @functools.cached_property
    def _starts(self) -> list[float]:
        return [s.start for s in self.spans]

    @functools.cached_property
    def _reach(self) -> list[float]:
        """The latest end among the spans up to each one."""
        return list(itertools.accumulate((s.end for s in self.spans), max))

    def gap_ns_by_span(self) -> dict[str | None, float]:
        """Idle time by the innermost program span the host was in at each
        gap's midpoint (None: in none), averaged over chips."""
        s = self.summary
        out: dict[str | None, float] = defaultdict(float)
        for c in s.chips.values():
            for lo, hi in c.gaps:
                out[self.span_at((lo + hi) / 2)] += (hi - lo) / len(s.chips)
        return dict(out)

    def scoped_op_ns(self, pick) -> float:
        """Leaf op time of the ops whose ``scopes`` ``pick`` accepts,
        averaged over chips."""
        return self.summary.op_ns(lambda text: pick(scopes(self.tf_op.get(text, ""))))


def load(path: str | Path, chips: list[int],
         window: tuple[float, float] | None = None) -> ProgramTrace:
    return ProgramTrace(summary=T.summarize(T.load(path), chips, window),
                        spans=load_spans(path), tf_op=read_tf_ops(path))


def readings(pt: ProgramTrace, steps: int, builds: int | None) -> dict:
    """The per-step readings of the window, given the ``matmul.builds`` the
    program counted in it. Each is None where the program keeps no counters
    (it predates its spans and scopes) and 0.0 where it built no program in
    the window. Where it built one and the trace holds no such span, or no
    op under a scope, that is an error: lost instrumentation is never read
    as a gain."""
    names = [f"entry_{s}_ms" for s in PHASES] + ["skew_ms", "unscoped_ms"]
    if builds is None:
        return dict.fromkeys(names + ["entry_builds_per_step"])
    out = {"entry_builds_per_step": builds / steps}
    if not builds:
        return out | dict.fromkeys(names, 0.0)
    for name in ["matmul.build"] + [f"matmul.{s}" for s in PHASES]:
        if pt.span_ns(name) == 0:
            raise RuntimeError(f"{builds} builds in the window, but the trace "
                               f"holds no span repro.{name}")
    for phase in PHASES:
        out[f"entry_{phase}_ms"] = pt.span_ns(f"matmul.{phase}") / steps * 1e-6
    out["entry_trace_ms"] += pt.span_ns("matmul.build") / steps * 1e-6
    if pt.scoped_op_ns(lambda s: s & SCOPES) == 0:
        raise RuntimeError(f"{builds} builds in the window, but the trace "
                           f"holds no op under a scope of {sorted(SCOPES)}")
    out["skew_ms"] = pt.scoped_op_ns(lambda s: "skew" in s) / steps * 1e-6
    out["unscoped_ms"] = pt.scoped_op_ns(lambda s: not s & SCOPES) / steps * 1e-6
    return out


# --------------------------------------------------------------------- run
def traced_run(bench, cell: str, seed: int, seconds: float, keep: Path,
               devices=None) -> tuple[dict, dict, ProgramTrace, int]:
    """``bench/run.py``'s traced run of a cell, through its ``run``: the
    result line, the counts, the trace (kept at ``keep``) and the
    ``matmul.builds`` the program counted in the window. ``devices`` is
    passed on to ``run``."""
    import jax

    from bench import run as harness
    from repro.runtime import tracing

    # The harness starts the profiler as its window opens and stops it as
    # the window closes: read the counters there, and copy the trace out of
    # the harness's temporary directory before it is removed.
    start_trace, stop_trace = jax.profiler.start_trace, jax.profiler.stop_trace
    window = {}

    def start(log_dir, **kwargs):
        window.update(dir=log_dir, before=tracing.counters())
        start_trace(log_dir, **kwargs)

    def stop():
        stop_trace()
        window["builds"] = (tracing.counters() - window["before"])["matmul.builds"]
        keep.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(T.find_xplane(window["dir"]), keep)

    with mock.patch.object(jax.profiler, "start_trace", start), \
            mock.patch.object(jax.profiler, "stop_trace", stop):
        result, counts = harness.run(bench, cell, seed, seconds, True, devices)
    chips = bench.cell(cell)["chips"]
    pt = load(keep, [d.id for d in (devices or jax.devices())[:chips]])
    return result, counts, pt, window["builds"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=Path, required=True,
                    help="where to write the trace (.xplane.pb)")
    ap.add_argument("--spec", type=Path, default=ROOT / "BENCHMARK.json")
    args = ap.parse_args(argv)

    from bench import run as harness

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    bench = harness.Bench(args.spec, [BENCH])
    try:
        result, counts, pt, builds = traced_run(
            bench, args.workload, args.seed, args.seconds, args.keep)
    except harness.NoDevice as e:
        print(f"bench/program_trace.py: {e}", file=sys.stderr)
        return 2
    steps = result["attempted"]
    out = readings(pt, steps, builds)
    out["window_step_ms"] = pt.summary.window_ns / steps * 1e-6
    out.update({(f"idle_s_in_{PREFIX}{name}" if name else "idle_s_outside_repro"):
                ns * 1e-9 for name, ns in pt.gap_ns_by_span().items()})
    tf_op = {T.instruction_name(text): op for text, op in pt.tf_op.items()}
    out["top_ops_ms"] = [(name, tf_op.get(name, ""), sec / steps * 1e3)
                         for name, sec in pt.summary.top_ops(20)]
    print(" ".join(f"{k}={v}" for k, v in counts.items()))
    print(json.dumps(result))
    print(json.dumps({"program": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
