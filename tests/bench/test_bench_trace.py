"""The trace reduction (``bench/trace.py``), on intervals small enough to
work out by hand and on a small trace recorded on a v5e:2x2 host."""
import collections

import pytest

from bench_helpers import ROOT
from bench import trace as T

RECORDED = ROOT / "tests/bench/data/cannon-1024.x4.xplane.pb"


def op(name, start, end, kind="fusion"):
    return T.Op(f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p)", start, end)


def test_interval_maths():
    assert T.union([(5, 9), (0, 2), (1, 3), (8, 12)], 0, 10) == [(0, 3), (5, 10)]
    assert T.length([(0, 3), (5, 10)]) == 8
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_leaves_drop_ops_that_contain_others():
    ops = [op("while", 0, 10, "while"), op("fusion.1", 1, 4), op("fusion.2", 5, 9),
           op("copy.1", 11, 12, "copy")]
    assert [o.text.split()[0] for o in T.leaves(ops)] == ["%fusion.1", "%fusion.2",
                                                         "%copy.1"]


def test_collectives_by_instruction():
    assert T.is_collective(op("collective-permute-done.3", 0, 1,
                              "collective-permute-done").text)
    assert T.is_collective(op("all-reduce", 0, 1, "all-reduce").text)
    assert not T.is_collective(op("fusion.7", 0, 1).text)


def test_summary_worked_by_hand():
    """One chip, window [0, 100): a loop 10-60 holding a fusion 10-30 and a
    collective 25-50; a fusion 70-80. Busy 10-60 and 70-80 = 60; idle gaps
    0-10 (in the dispatch span), 60-70 and 80-100 (in the block span).
    The collective runs 25-50; the fusion covers it to 30, the loop is not a
    leaf, so 20 of its 25 are exposed."""
    trace = T.Trace(
        ops={0: [op("while", 10, 60, "while"), op("fusion.1", 10, 30),
                 op("collective-permute.1", 25, 50, "collective-permute"),
                 op("fusion.2", 70, 80)]},
        spans=[T.Span("dispatch", 0, 12), T.Span("block", 12, 100)])
    s = T.summarize(trace, [0], (0, 100))
    c = s.chips[0]
    assert c.busy_ns == 60 and s.window_ns == 100
    assert c.collective_ns == 25 and c.exposed_collective_ns == 20
    assert c.gaps == [(0, 10), (60, 70), (80, 100)]
    assert s.gap_ns_by_span() == {"dispatch": 10, "block": 30}
    assert s.longest_gaps(2) == [("block", 20e-9), ("dispatch", 10e-9)]
    assert [n for n, _ in s.top_ops(3)] == ["collective-permute.1", "fusion.1",
                                            "fusion.2"]
    assert s.op_ns(lambda text: "fusion(" in text) == 30


# Plain sums over the raw events of the recorded trace: two calls of a
# 1024^3 Cannon on a 2x2 grid (512^3 blocks), each under a dispatch and a
# block span. On each chip the ops line runs one op at a time, so busy time
# is the sum of the top-level ops (the loop counted once, not with its
# body), collective time the sum of the 24 collective-permute start and
# done events, all of it exposed, and the block products are the 4 events
# of ``convolution_add_fusion.2``. Every gap falls in a dispatch span: the
# program runs at the end of each call, before the call returns.
WINDOW = (155920394.0, 269952140.0)
BUSY = {0: 93235, 1: 91620, 2: 91675, 3: 91475}
COLLECTIVE = {0: 76261, 1: 72437, 2: 74677, 3: 72232}
MATMUL = {0: 7692, 1: 7694, 2: 7697, 3: 7692}
LONGEST_GAP = 56025314  # chip 3, the first call's dispatch


@pytest.fixture(scope="module")
def recorded():
    return T.summarize(T.load(RECORDED), [0, 1, 2, 3])


def test_recorded_trace_reduces_to_hand_sums(recorded):
    s = recorded
    assert s.window == WINDOW
    assert [x.name for x in s.spans] == ["dispatch", "block", "dispatch", "block"]
    for chip, c in s.chips.items():
        assert c.busy_ns == BUSY[chip]
        assert c.collective_ns == c.exposed_collective_ns == COLLECTIVE[chip]
        assert T.length(c.gaps) == s.window_ns - BUSY[chip]
    mean_idle = s.window_ns - sum(BUSY.values()) / 4
    assert s.gap_ns_by_span() == pytest.approx({"dispatch": mean_idle})
    assert s.longest_gaps(1) == [("dispatch", LONGEST_GAP * 1e-9)]
    assert s.top_ops(1)[0][0].startswith("collective-permute-done")


def test_metric_readers_on_recorded_trace(recorded):
    from bench import run as harness

    b = harness.Bench(ROOT / "BENCHMARK.json", [ROOT / "bench"])
    peaks = b.peaks["TPU v5 lite"]
    steps, q, n = 2, 2, 1024
    ctx = harness.Context(
        summary=recorded, steps=steps, window_s=recorded.window_ns * 1e-9,
        chips=4, peaks=peaks, dispatch_s=0.1,
        work={"flops": 2 * n ** 3, "hbm_bytes": 12 * n * n,
              "matmul_flops_per_chip": q * 2 * (n // q) ** 3},
        counters=collections.Counter(), spans=T.load(RECORDED).program,
        tf_op=T.read_tf_ops(RECORDED))
    read = {m: b.module("metrics", m).read(ctx) for m in (
        "matmul_roofline", "collective_exposed_ms", "device_idle.step", "mfu",
        "dispatch_ms", "skew_ms", "entry_cache_hits")}
    matmul_s = sum(MATMUL.values()) / 4 * 1e-9
    assert read["matmul_roofline"] == pytest.approx(
        100 * steps * q * 2 * 512 ** 3 / peaks["bf16_flops_per_s"] / matmul_s)
    assert read["collective_exposed_ms"] == pytest.approx(
        sum(COLLECTIVE.values()) / 4 / steps * 1e-6)
    assert read["device_idle.step"] == pytest.approx(
        100 * (1 - sum(BUSY.values()) / 4 / (WINDOW[1] - WINDOW[0])))
    # At this size the step's 12 MiB of A, B and C bound it, not its FLOPs.
    least_s = max(2 * n ** 3 / peaks["bf16_flops_per_s"],
                  12 * n * n / peaks["hbm_bytes_per_s"]) / 4
    assert least_s == 12 * n * n / peaks["hbm_bytes_per_s"] / 4
    assert read["mfu"] == pytest.approx(
        100 * least_s / ((WINDOW[1] - WINDOW[0]) * 1e-9 / steps))
    assert read["dispatch_ms"] == pytest.approx(50.0)
    assert 0 < read["matmul_roofline"] <= 100 and 0 < read["mfu"] <= 100
    # Recorded before the program had scopes and counters: nothing to read.
    assert read["skew_ms"] is None and read["entry_cache_hits"] is None
