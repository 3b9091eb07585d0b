"""Reduce a profiler trace (``.xplane.pb``) to device-op intervals per chip.

A traced run records, on each chip's plane ``/device:TPU:<n>``, a line
``XLA Ops`` whose events are the HLO instructions that ran, named by their
HLO text (``%fusion.3 = f32[...] fusion(...)``). Control flow nests: a
``while`` event spans the events of its body. The host's plane holds the
harness spans (``bench.dispatch``, ``bench.block``) on the same clock.

From those this module computes, for each chip and inside a window:

- busy time: the union of all op intervals;
- op time by instruction: the durations of the leaf ops (ops that contain
  no other op), so that a loop is not counted on top of its body;
- collective time, and the part of it during which no other op runs on that
  chip (exposed collective time);
- idle gaps, each attributed to the harness span the host was in at the
  gap's midpoint.

Times are in nanoseconds, as ``jax.profiler.ProfileData`` gives them.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
#: HLO opcodes (and their async halves) that move data between chips.
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "collective-broadcast",
               "send", "recv")
_INSTR = re.compile(r"^%([^\s=]+)")


def instruction_name(text: str) -> str:
    """``fusion.3`` from an op's HLO text ``%fusion.3 = f32[...] fusion(...)``."""
    m = _INSTR.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def is_collective(text: str) -> bool:
    kind = re.sub(r"\.\d+$", "", instruction_name(text))
    return kind.startswith(COLLECTIVES)


@dataclasses.dataclass(frozen=True)
class Op:
    text: str          # the op's HLO text, as the trace names the event
    start: float
    end: float


@dataclasses.dataclass(frozen=True)
class Span:
    name: str          # harness span without the "bench." prefix
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Op]]          # chip -> every op event, nested ones too
    spans: list[Span]


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[int, list[Op]] = {}
    spans: list[Span] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    Op(e.name, e.start_ns, e.end_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(
                    Span(e.name[len(SPAN_PREFIX):], e.start_ns, e.end_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for chip_ops in ops.values():
        chip_ops.sort(key=lambda o: (o.start, -o.end))
    spans.sort(key=lambda s: s.start)
    return Trace(ops=ops, spans=spans)


def find_xplane(directory: str | Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {len(found)}")
    return found[0]


# ------------------------------------------------------------ interval maths
def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list[tuple[float, float]]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(ops: list[Op]) -> list[Op]:
    """Ops that contain no other op (``ops`` sorted by start, longest first,
    so that a loop's first child follows it)."""
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt.start < op.end and nxt.end <= op.end)]


# ----------------------------------------------------------------- reduction
@dataclasses.dataclass
class ChipSummary:
    busy_ns: float
    op_ns: dict[str, float]           # leaf op time by HLO text
    collective_ns: float
    exposed_collective_ns: float
    gaps: list[tuple[float, float]]   # idle intervals inside the window


@dataclasses.dataclass
class Summary:
    window: tuple[float, float]
    chips: dict[int, ChipSummary]
    spans: list[Span]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def mean(self, field: str) -> float:
        return sum(getattr(c, field) for c in self.chips.values()) / len(self.chips)

    def op_ns(self, pick) -> float:
        """Leaf op time of the ops whose HLO text ``pick`` accepts, averaged
        over chips."""
        return sum(t for c in self.chips.values() for n, t in c.op_ns.items()
                   if pick(n)) / len(self.chips)

    def top_ops(self, k: int = 10) -> list[tuple[str, float]]:
        """Instructions by leaf time, averaged over chips, in seconds."""
        total: dict[str, float] = defaultdict(float)
        for c in self.chips.values():
            for n, t in c.op_ns.items():
                total[instruction_name(n)] += t / len(self.chips)
        return [(n, t * 1e-9) for n, t in
                sorted(total.items(), key=lambda kv: -kv[1])[:k]]

    def span_at(self, t: float) -> str:
        """The harness span the host was in at ``t`` ("host" if none)."""
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self.spans[i].end:
            return self.spans[i].name
        return "host"

    @functools.cached_property
    def _starts(self) -> list[float]:
        return [s.start for s in self.spans]

    def longest_gaps(self, k: int = 10) -> list[tuple[str, float]]:
        """The longest idle gaps over all chips, named by the harness span
        the host was in at each gap's midpoint, in seconds."""
        gaps = sorted((g for c in self.chips.values() for g in c.gaps),
                      key=lambda g: g[0] - g[1])[:k]
        return [(self.span_at((s + e) / 2), (e - s) * 1e-9) for s, e in gaps]

    def gap_ns_by_span(self) -> dict[str, float]:
        """Idle time by harness span, averaged over chips."""
        out: dict[str, float] = defaultdict(float)
        for c in self.chips.values():
            for s, e in c.gaps:
                out[self.span_at((s + e) / 2)] += (e - s) / len(self.chips)
        return dict(out)


def summarize(trace: Trace, chips: list[int],
              window: tuple[float, float] | None = None) -> Summary:
    """Reduce ``trace`` over ``chips`` inside ``window`` (default: from the
    first harness span's start to the last one's end)."""
    if window is None:
        if not trace.spans:
            raise RuntimeError("the trace holds no harness span")
        window = (trace.spans[0].start, max(s.end for s in trace.spans))
    lo, hi = window
    out = {}
    for chip in chips:
        ops = trace.ops.get(chip, [])
        busy = union(((o.start, o.end) for o in ops), lo, hi)
        op_ns: dict[str, float] = defaultdict(float)
        for o in leaves(ops):
            op_ns[o.text] += max(0.0, min(o.end, hi) - max(o.start, lo))
        coll = union(((o.start, o.end) for o in ops if is_collective(o.text)), lo, hi)
        other = union(((o.start, o.end) for o in leaves(ops)
                       if not is_collective(o.text)), lo, hi)
        out[chip] = ChipSummary(
            busy_ns=length(busy), op_ns=dict(op_ns),
            collective_ns=length(coll),
            exposed_collective_ns=length(subtract(coll, other)),
            gaps=subtract([(lo, hi)], busy))
    return Summary(window=window, chips=out, spans=trace.spans)
