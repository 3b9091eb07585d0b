"""The program's own instrumentation read from a trace: the ``tf_op``
metadata reader and the scope and span attribution that metric readers get
(``bench/trace.py``, ``bench/run.py``'s ``Context``), and the cut of each
call that builds into phases with its readings (``bench/program_trace.py``),
worked by hand, on two v5e:2x2 traces (recorded before the program had
spans or scopes, and with them), and on a traced run on CPU devices."""
import collections
import json
import textwrap

import pytest

from bench_helpers import ROOT, run_python, write_tiny_bench
from bench import program_trace as PT
from bench import run as harness
from bench import trace as T

RECORDED = ROOT / "tests/bench/data/cannon-1024.x4.xplane.pb"
#: Recorded on a v5e:2x2 with the program's spans and scopes: two calls.
SCOPED = ROOT / "tests/bench/data/cannon-1024.x4.scoped.xplane.pb"
SKEW = "jit(body)/shard_map/skew/while/body/closed_call/"


# ------------------------------------------------- a raw XSpace, by hand
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _bytes(field: int, payload: bytes | str) -> bytes:
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name: str, events: dict[str, str], by_ref: bool = False) -> bytes:
    """An XPlane whose event metadata give each HLO text its tf_op ("" for
    none), as a string or, ``by_ref``, as a reference to a stat metadata."""
    names = {1: "hlo_category", 2: "tf_op"}
    out = _bytes(2, name) + _bytes(3, b"\x08\x01 a line, skipped")
    for i, (text, tf_op) in enumerate(events.items(), start=1):
        stats = _bytes(5, _int(1, 1) + _bytes(5, "fusion"))
        if tf_op and by_ref:
            names[100 + i] = tf_op
            stats += _bytes(5, _int(1, 2) + _int(7, 100 + i))
        elif tf_op:
            stats += _bytes(5, _int(1, 2) + _bytes(5, tf_op))
        meta = _int(1, i) + _bytes(2, text) + stats
        out += _bytes(4, _int(1, i) + _bytes(2, meta))
    for i, n in names.items():
        out += _bytes(5, _int(1, i) + _bytes(2, _int(1, i) + _bytes(2, n)))
    return out


def _xspace(tmp_path, *planes: bytes):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(b"".join(_bytes(1, p) for p in planes) + _bytes(4, "host"))
    return path


def test_tf_ops_read_from_a_raw_xspace(tmp_path):
    path = _xspace(
        tmp_path,
        _plane("/device:TPU:0", {"%a": SKEW + "shift/ppermute:", "%b": "", "%c": ""}),
        _plane("/device:TPU:1", {"%a": SKEW + "shift/ppermute:",
                                 "%c": "jit(body)/dot_general:"}, by_ref=True),
        _plane("/host:CPU", {"%a": "a host event, not read"}))
    assert T.read_tf_ops(path) == {"%a": SKEW + "shift/ppermute:", "%b": "",
                                    "%c": "jit(body)/dot_general:"}


def test_two_tf_ops_for_one_hlo_text_are_an_error(tmp_path):
    path = _xspace(tmp_path, _plane("/device:TPU:0", {"%a": "x/ppermute:"}),
                   _plane("/device:TPU:1", {"%a": "y/ppermute:"}))
    with pytest.raises(ValueError, match="two tf_ops"):
        T.read_tf_ops(path)


def test_scopes_are_the_components_of_the_path():
    assert T.scopes(SKEW + "shift/ppermute:") == {
        "jit(body)", "shard_map", "skew", "while", "body", "closed_call",
        "shift", "ppermute"}
    assert T.scopes("") == set()


# ------------------------------------------------------------ worked by hand
def op(name, start, end, kind="fusion"):
    return T.Op(f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p)", start, end)


def hand_trace(marks: bool = True) -> PT.ProgramTrace:
    """One chip, window [0, 100): a skew loop 10-60 holding its select
    10-30 and its shift 25-50, the step's fused product 70-80, an inserted
    copy 85-90 that no scope names. Idle gaps 0-10, 60-70, 80-85, 90-100.
    Program spans: a build 0-3, then JAX's jit call 3-68 (twice, as JAX
    records it, and a nested call 20-21), which lowers 8-50 and runs the
    executable from 66; a second build 96-97 and call 97-130, lowering
    105-120 and running from 125, that the window cuts at 100. Without
    ``marks`` the trace lost JAX's lowering and run events."""
    ops = [op("while", 10, 60, "while"), op("fusion.1", 10, 30),
           op("collective-permute.1", 25, 50, "collective-permute"),
           op("fusion.2", 70, 80), op("copy.1", 85, 90, "copy")]
    tf_op = {ops[0].text: "jit(body)/shard_map/skew/while:",
             ops[1].text: SKEW + "jit(_where)/select_n:",
             ops[2].text: SKEW + "shift/ppermute:",
             ops[3].text: "jit(body)/shard_map/while/body/closed_call/local_matmul/add:",
             ops[4].text: ""}
    summary = T.summarize(T.Trace(ops={0: ops}, spans=[T.Span("dispatch", 0, 100)]),
                          [0], (0, 100))
    spans = [T.Span("matmul.build", 0, 3), T.Span("matmul.build", 96, 97)]
    jax_events = [T.Span("PjitFunction(body)", 3, 68), T.Span("PjitFunction(body)", 3, 68),
                  T.Span("PjitFunction(add)", 20, 21), T.Span("PjitFunction(body)", 97, 130)]
    if marks:
        jax_events += [T.Span(PT.LOWER, 8, 50), T.Span(PT.EXECUTE, 66, 67),
                       T.Span(PT.LOWER, 105, 120), T.Span(PT.EXECUTE, 125, 129)]
    spans += PT.phases(spans, jax_events)
    spans.sort(key=lambda s: (s.start, -s.end))
    return PT.ProgramTrace(summary=summary, spans=spans, tf_op=tf_op)


def context(pt: PT.ProgramTrace, counters: dict | None = None) -> harness.Context:
    """What the harness hands a metric reader for ``pt``'s window of two
    steps: the program's spans without the phases cut from JAX's events."""
    phases = {f"matmul.{p}" for p in PT.PHASES}
    return harness.Context(
        summary=pt.summary, steps=2, window_s=pt.summary.window_ns * 1e-9,
        chips=len(pt.summary.chips), work={}, peaks={}, dispatch_s=0.0,
        counters=collections.Counter(counters or {}),
        spans=[s for s in pt.spans if s.name not in phases], tf_op=pt.tf_op)


def read(metric: str, ctx: harness.Context):
    b = harness.Bench(ROOT / "BENCHMARK.json", [ROOT / "bench"])
    return b.module("metrics", metric).read(ctx)


def test_scope_attribution_worked_by_hand():
    ctx = context(hand_trace())
    assert ctx.scoped_op_ns(lambda s: "skew" in s) == 20 + 25
    assert ctx.scoped_op_ns(lambda s: "local_matmul" in s) == 10
    assert ctx.scoped_op_ns(lambda s: not s & PT.SCOPES) == 5
    assert read("skew_ms", ctx) == pytest.approx(45 / 2 * 1e-6)


def test_calls_cut_into_phases_at_jaxs_events():
    assert [(s.name, s.start, s.end) for s in hand_trace().spans[:5]] == [
        ("matmul.build", 0, 3), ("matmul.trace", 3, 8),
        ("matmul.lower", 8, 50), ("matmul.load", 50, 66),
        ("matmul.launch", 66, 68)]
    assert {s.name for s in hand_trace(marks=False).spans} == {"matmul.build"}


def test_program_spans_worked_by_hand():
    pt = hand_trace()
    assert pt.summary.chips[0].gaps == [(0, 10), (60, 70), (80, 85), (90, 100)]
    assert [pt.span_ns(f"matmul.{s}") for s in PT.PHASES] == [5 + 3, 42, 16, 2]
    assert context(pt).span_ns("matmul.build") == 3 + 1
    assert context(pt).span_ns("matmul.call") == 0
    # Midpoints 5, 65, 82.5, 95: the innermost span holding each.
    assert pt.gap_ns_by_span() == {"matmul.trace": 10, "matmul.load": 10,
                                   None: 15}


@pytest.mark.parametrize("builds,expected", [
    (None, dict.fromkeys(["entry_builds_per_step", "entry_trace_ms",
                          "entry_lower_ms", "entry_load_ms", "entry_launch_ms",
                          "unscoped_ms"])),
    (0, {"entry_builds_per_step": 0.0, "entry_trace_ms": 0.0,
         "entry_lower_ms": 0.0, "entry_load_ms": 0.0, "entry_launch_ms": 0.0,
         "unscoped_ms": 5 / 2 * 1e-6}),
    (2, {"entry_builds_per_step": 1.0, "entry_trace_ms": (8 + 4) / 2 * 1e-6,
         "entry_lower_ms": 42 / 2 * 1e-6, "entry_load_ms": 16 / 2 * 1e-6,
         "entry_launch_ms": 2 / 2 * 1e-6, "unscoped_ms": 5 / 2 * 1e-6}),
])
def test_readings_worked_by_hand(builds, expected):
    """Scope readings do not depend on builds; the phases are 0.0 where
    the window built nothing, and nothing is read without counts."""
    assert PT.readings(hand_trace(), 2, builds) == pytest.approx(expected)


@pytest.mark.parametrize("lost,missing", [
    ("span", "repro.matmul.build"), ("jax events", "repro.matmul.trace"),
    ("scopes", "no op under a scope")])
def test_lost_instrumentation_raises(lost, missing):
    pt = hand_trace(marks=lost != "jax events")
    if lost == "span":
        pt.spans = [s for s in pt.spans if s.name != "matmul.build"]
    elif lost == "scopes":
        pt.tf_op = {text: "jit(body)/shard_map/while:" for text in pt.tf_op}
    with pytest.raises(RuntimeError, match=missing):
        PT.readings(pt, 2, 2)


# --------------------------------------------- the recorded v5e:2x2 trace
@pytest.fixture(scope="module")
def recorded():
    return PT.load(RECORDED, [0, 1, 2, 3])


def test_recorded_trace_gives_every_op_one_tf_op(recorded):
    ops = {o.text for chip in T.load(RECORDED).ops.values() for o in chip}
    assert ops and ops <= set(recorded.tf_op)
    permutes = [t for t in ops if T.is_collective(t)]
    assert permutes and all(recorded.tf_op[t].endswith("ppermute:")
                            for t in permutes)
    products = [t for t in ops if "convolution_add_fusion" in t]
    assert products and all(recorded.tf_op[t].endswith("dot_general:")
                            for t in products)


def test_recorded_trace_predates_the_programs_spans(recorded):
    """Recorded before the program had spans or scopes: nothing to read
    without its counters; with them, its ops under no scope are an error
    whatever was built, and so are builds without their span. The metric
    readers of the scope and the counters read nothing."""
    assert recorded.spans == []
    assert set(PT.readings(recorded, 2, None).values()) == {None}
    with pytest.raises(RuntimeError, match="no op under a scope"):
        PT.readings(recorded, 2, 0)
    with pytest.raises(RuntimeError, match="no span repro.matmul.build"):
        PT.readings(recorded, 2, 2)
    ctx = context(recorded)
    assert read("skew_ms", ctx) is None
    assert read("entry_cache_hits", ctx) is None


@pytest.fixture(scope="module")
def scoped():
    return PT.load(SCOPED, [0, 1, 2, 3])


def test_scoped_trace_reads_hand_sums(scoped):
    """The readings of the trace with spans and scopes, against sums worked
    over its raw events: each call (JAX's first jit call after each build)
    cut at the start and end of JAX's lowering and the start of its run;
    leaf ops by their tf_op, clipped to the window, mean over the four
    chips. (The entry it was recorded with also spanned its call, as
    ``repro.matmul.call``; the reader does not need that span.)"""
    assert scoped.summary.window == (148117103.0, 274259137.0)
    assert [s.name for s in scoped.spans if s.name == "matmul.build"] == [
        "matmul.build"] * 2
    assert PT.readings(scoped, 2, 2) == pytest.approx({
        "entry_builds_per_step": 1.0,
        "entry_trace_ms": (446449 + 10659620) / 2 * 1e-6,
        "entry_lower_ms": 76676300 / 2 * 1e-6,
        "entry_load_ms": 30726557 / 2 * 1e-6,
        "entry_launch_ms": 2896850 / 2 * 1e-6,
        "unscoped_ms": 9337.75 / 2 * 1e-6})
    jit_calls = [(148458612, 206203106), (209047655, 272262488)]
    assert [(s.start, s.end) for s in scoped.spans if s.name == "matmul.trace"][0][0] \
        == jit_calls[0][0]
    assert sum(scoped.span_ns(f"matmul.{p}") for p in PT.PHASES) == sum(
        hi - lo for lo, hi in jit_calls)


@pytest.mark.parametrize("calls,builds,hits", [(2, 2, 0.0), (4, 1, 75.0), (2, 0, 100.0)])
def test_metric_readers_on_scoped_trace(scoped, calls, builds, hits):
    """``skew_ms`` sums the same leaf ops under ``skew`` as the hand sums
    above; ``entry_cache_hits`` is the share of calls that built nothing."""
    ctx = context(scoped, {"matmul.calls": calls, "matmul.builds": builds})
    assert read("skew_ms", ctx) == pytest.approx(25328.75 / 2 * 1e-6)
    assert read("entry_cache_hits", ctx) == pytest.approx(hits)
    assert ctx.span_ns("matmul.build") > 0


def test_scoped_trace_names_every_shift(scoped):
    ops = {o.text for chip in T.load(SCOPED).ops.values() for o in chip}
    permutes = [t for t in ops if T.is_collective(t)]
    assert permutes and all("/shift/ppermute:" in scoped.tf_op[t] for t in permutes)
    assert any("/skew/" in scoped.tf_op[t] for t in permutes)
    assert any("/skew/" not in scoped.tf_op[t] for t in permutes)


# ------------------------------------------------- a traced run on the CPU
@pytest.fixture(scope="module")
def cpu_run(tmp_path_factory):
    """A traced run of the tiny x4 cell through the harness, its trace kept,
    and the program's counts over the whole process beside the window's."""
    tmp = tmp_path_factory.mktemp("program_trace")
    spec = write_tiny_bench(tmp)
    kept = tmp / "kept.xplane.pb"
    script = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
        from pathlib import Path
        import jax
        from bench import program_trace as PT, run as harness, trace as T
        from repro.runtime import tracing
        b = harness.Bench(Path({str(spec)!r}), [Path({str(ROOT / "bench")!r})])
        before = tracing.counters()
        result, counts = harness.run(b, "cannon-16384.x4", 3_000_000_019, 0.3, True,
                                     devices=jax.devices(), keep=Path({str(kept)!r}))
        process = tracing.counters() - before
        pt = PT.load({str(kept)!r}, [d.id for d in jax.devices()[:4]])
        print(json.dumps({{
            "correct": result["correct"], "counts": counts,
            "metrics": result["metrics"], "process": process,
            "readings": PT.readings(pt, result["attempted"], 0),
            "harness": [(s.name, s.start, s.end) for s in pt.summary.spans],
            "program": [(s.name, s.start, s.end) for s in pt.spans]}}))
    """)
    proc = run_python(["-c", script], tmp)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_cpu_run_builds_in_warmup_only(cpu_run):
    """The warm-up call builds the program once; the window's calls, as the
    harness's own counters read them, build none and are all counted."""
    counts = cpu_run["counts"]
    assert cpu_run["correct"] and counts["calls"] >= 2
    assert cpu_run["process"]["matmul.builds"] == 1
    assert "counter.matmul.builds" not in counts
    assert counts["counter.matmul.calls"] == counts["calls"]
    assert cpu_run["metrics"]["entry_cache_hits"]["value"] == 100.0


def test_cpu_run_dispatch_spans_hold_no_build(cpu_run):
    """No dispatch span of the window holds a ``repro.matmul.build``, so
    no call is cut into phases and they read 0.0; a CPU trace holds no op
    on a chip, so no scope reading is made."""
    dispatch = [s for s in cpu_run["harness"] if s[0] == "dispatch"]
    assert len(dispatch) == cpu_run["counts"]["calls"]
    builds = [s for s in cpu_run["program"] if s[0] == "matmul.build"]
    assert not [b for b in builds for _, lo, hi in dispatch if lo <= b[1] and b[2] <= hi]
    assert not [s for s in cpu_run["program"] if s[0].removeprefix("matmul.") in PT.PHASES]
    assert cpu_run["readings"] == {
        "entry_trace_ms": 0.0, "entry_lower_ms": 0.0, "entry_load_ms": 0.0,
        "entry_launch_ms": 0.0, "entry_builds_per_step": 0.0, "unscoped_ms": None}
    assert "skew_ms" not in cpu_run["metrics"]


def test_without_a_chip_the_command_fails(tmp_path):
    proc = run_python(["bench/program_trace.py", "--workload", "cannon-16384.x1",
                       "--seed", "3000000001", "--seconds", "1",
                       "--keep", str(tmp_path / "kept.xplane.pb")], tmp_path, devices=1)
    assert proc.returncode == 2
    assert "no accelerator" in proc.stderr
