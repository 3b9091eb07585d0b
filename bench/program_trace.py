"""The matmul entry's host phases in a window that builds, and a traced run
of a cell that keeps its trace to read them.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s> \\
        --keep <path.xplane.pb> [--spec <BENCHMARK.json>]

The program (``repro.runtime.tracing``) spans each build of a matmul
program as ``repro.matmul.build`` (the ``shard_map`` and ``jax.jit``, made
on the first call with a key the grid has not seen, which then calls it)
and counts ``matmul.builds`` and ``matmul.calls``; its named scopes
(``skew``, ``shift``, ``local_matmul``) reach each op's ``tf_op``. The
harness reads those for its metrics (``bench/trace.py``, ``Context``). This
module reads what no metric reads: it cuts each call that built into
phases at JAX's own events, and from them, per step of a window:

- ``entry_<phase>_ms``: host time in each phase of the calls that built
  (``trace`` holds the build too);
- ``entry_builds_per_step``: programs the entry built per step;
- ``unscoped_ms``: device leaf-op time under none of the scopes, mean over
  chips;

and the device's idle time by the innermost program span the host was in.

The command runs the cell once as ``bench/run.py --workload <cell> --trace 1``
does, through its ``run``, keeping the trace at ``--keep``, and prints the
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
import sys
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":
    # Import this checkout's code, and never bench/trace.py as "trace".
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]

from bench import trace as T  # noqa: E402

#: The program's named scopes on the device's work (``jax.named_scope``).
SCOPES = frozenset({"skew", "shift", "local_matmul"})
PHASES = ("trace", "lower", "load", "launch")
#: JAX's own host events: a jit call (``PjitFunction(<name>)``), and in it
#: the call's lowering to StableHLO and its executable's run. They are no
#: stable interface of JAX: ``readings`` fails where the calls lost them.
CALL, LOWER, EXECUTE = "PjitFunction(", "lower_sharding_computation", "ExecuteReplicated.__call__"


def jax_events(path: str | Path) -> list[T.Span]:
    """JAX's jit calls, lowerings and executable runs on the host."""
    from jax.profiler import ProfileData

    return [T.Span(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(CALL) or e.name in (LOWER, EXECUTE)]


def phases(spans: list[T.Span], jax_events: list[T.Span]) -> list[T.Span]:
    """Each call of the entry that built cut at JAX's events in it into four
    spans. The call is the first jit call to start once a ``matmul.build``
    span has ended (the longest of those that start together), since the
    entry calls the program it has just built. ``matmul.trace`` runs up to
    the lowering, ``matmul.lower`` is the lowering, ``matmul.load`` runs
    from there to the executable's run (the persistent-cache read and the
    load onto each device, which JAX leaves unannotated, or a backend
    compile on a miss) and ``matmul.launch`` from the run to the call's end.
    A call without a lowering and a run in it gets none."""
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


# ----------------------------------------------------------------- reduction
@dataclasses.dataclass
class ProgramTrace:
    summary: T.Summary                # the harness's reduction of the trace
    spans: list[T.Span]               # the program's and the phases, by start
    tf_op: dict[str, str]             # HLO text -> tf_op

    def span_ns(self, name: str) -> float:
        return T.span_ns(self.spans, name, self.summary.window)

    def scoped_op_ns(self, pick) -> float:
        return T.scoped_op_ns(self.summary, self.tf_op, pick)

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


def load(path: str | Path, chips: list[int],
         window: tuple[float, float] | None = None) -> ProgramTrace:
    trace = T.load(path)
    spans = trace.program + phases(trace.program, jax_events(path))
    return ProgramTrace(summary=T.summarize(trace, chips, window),
                        spans=sorted(spans, key=lambda s: (s.start, -s.end)),
                        tf_op=T.read_tf_ops(path))


def readings(pt: ProgramTrace, steps: int, builds: int | None) -> dict:
    """The per-step readings of the window, given the ``matmul.builds`` the
    program counted in it: each None where it counted no ``matmul.calls``
    (it predates its spans, scopes and counters).

    The phases are 0.0 where the window built no program, and an error
    where it built one and the trace holds no such span. ``unscoped_ms``
    does not depend on builds: None where no op ran on a chip (a CPU
    trace), and an error where ops ran but none under a scope. Lost
    instrumentation is never read as a gain."""
    names = [f"entry_{s}_ms" for s in PHASES]
    if builds is None:
        return dict.fromkeys(names + ["entry_builds_per_step", "unscoped_ms"])
    out = dict.fromkeys(names, 0.0)
    if builds:
        for name in ["matmul.build"] + [f"matmul.{s}" for s in PHASES]:
            if pt.span_ns(name) == 0:
                raise RuntimeError(f"{builds} builds in the window, but the "
                                   f"trace holds no span repro.{name}")
        out = {f"entry_{p}_ms": pt.span_ns(f"matmul.{p}") / steps * 1e-6
               for p in PHASES}
        out["entry_trace_ms"] += pt.span_ns("matmul.build") / steps * 1e-6
    out["entry_builds_per_step"] = builds / steps
    out["unscoped_ms"] = None
    if pt.summary.mean("busy_ns"):
        if pt.scoped_op_ns(lambda s: s & SCOPES) == 0:
            raise RuntimeError("the trace holds ops, but no op under a scope "
                               f"of {sorted(SCOPES)}")
        out["unscoped_ms"] = pt.scoped_op_ns(lambda s: not s & SCOPES) / steps * 1e-6
    return out


# --------------------------------------------------------------------- run
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
        result, counts = harness.run(bench, args.workload, args.seed,
                                     args.seconds, True, keep=args.keep)
    except harness.NoDevice as e:
        print(f"bench/program_trace.py: {e}", file=sys.stderr)
        return 2
    import jax

    chips = bench.cell(args.workload)["chips"]
    pt = load(args.keep, [d.id for d in jax.devices()[:chips]])
    builds = (counts.get("counter.matmul.builds", 0)
              if "counter.matmul.calls" in counts else None)
    steps = result["attempted"]
    out = readings(pt, steps, builds)
    out["window_step_ms"] = pt.summary.window_ns / steps * 1e-6
    out.update({(f"idle_s_in_{T.PROGRAM_PREFIX}{name}" if name else "idle_s_outside_repro"):
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
