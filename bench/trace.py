"""Reduce a profiler trace (``.xplane.pb``) to device-op intervals per chip.

A traced run records, on each chip's plane ``/device:TPU:<n>``, a line
``XLA Ops`` whose events are the HLO instructions that ran, named by their
HLO text (``%fusion.3 = f32[...] fusion(...)``). Control flow nests: a
``while`` event spans the events of its body. The host's plane holds the
harness spans (``bench.dispatch``, ``bench.block``) and the program's own
(``repro.<name>``, ``repro.runtime.tracing``) on the same clock. Each op's
metadata carries a ``tf_op`` stat, the path of transforms and named scopes
it was traced under (``jit(body)/shard_map/skew/while/.../ppermute:``);
``jax.profiler.ProfileData`` does not give it, so ``read_tf_ops`` reads it
from the raw protobuf.

From those this module computes, for each chip and inside a window:

- busy time: the union of all op intervals;
- op time by instruction: the durations of the leaf ops (ops that contain
  no other op), so that a loop is not counted on top of its body;
- collective time, and the part of it during which no other op runs on that
  chip (exposed collective time);
- idle gaps, each attributed to the harness span the host was in at the
  gap's midpoint;
- host time in the program's spans, and leaf-op time under its named
  scopes.

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
PROGRAM_PREFIX = "repro."
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
    #: the program's spans, without the "repro." prefix, ordered by start
    #: and, at one start, longest first
    program: list[Span] = dataclasses.field(default_factory=list)


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[int, list[Op]] = {}
    spans: list[Span] = []
    program: list[Span] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    Op(e.name, e.start_ns, e.end_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    for prefix, out in ((SPAN_PREFIX, spans), (PROGRAM_PREFIX, program)):
                        if e.name.startswith(prefix):
                            out.append(Span(e.name[len(prefix):], e.start_ns, e.end_ns))
    for chip_ops in ops.values():
        chip_ops.sort(key=lambda o: (o.start, -o.end))
    spans.sort(key=lambda s: s.start)
    program.sort(key=lambda s: (s.start, -s.end))
    return Trace(ops=ops, spans=spans, program=program)


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


# ---------------------------------------------------- the program's own marks
def span_ns(spans: list[Span], name: str, window: tuple[float, float]) -> float:
    """Host time in the spans called ``name``, clipped to ``window``."""
    lo, hi = window
    return sum(max(0.0, min(s.end, hi) - max(s.start, lo))
               for s in spans if s.name == name)


def scopes(tf_op: str) -> set[str]:
    """The components of a ``tf_op`` path: the named scopes the op ran
    under, among the names of the transforms and control flow."""
    return set(tf_op.rsplit(":", 1)[0].split("/")) if tf_op else set()


def scoped_op_ns(summary: Summary, tf_op: dict[str, str], pick) -> float:
    """Leaf op time of the ops whose ``scopes`` ``pick`` accepts, averaged
    over chips. An op ``tf_op`` does not name is under no scope."""
    return summary.op_ns(lambda text: pick(scopes(tf_op.get(text, ""))))


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
        if not DEVICE_PLANE.match(name):
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
