"""Drive runs of tiny cells on CPU devices, with the timed path sound or
broken underneath, and print one JSON line per scenario.

    python tests/bench/run_tiny.py <spec> [--overlay DIR] <scenario>:<cell> ...

Scenarios: ``sound`` (a run; also prints the driver's counts per step),
``traced`` (a run under the profiler; prints its per-layer metrics and the
program's counters), ``readings`` (the control's readings against the
limits, as ``bench/calibrate.py`` reads them), ``control`` (a run with the
control in the program's place), and each fault that the driver's
``tests/bench/faults/<driver>.py`` plants. A run skips the harness's look
for a chip and otherwise runs as on the chip. ``--overlay`` names a
directory laid out like the repository whose ``bench/``,
``tests/bench/faults/`` and ``src/`` are searched first
(``bench_helpers``).
"""
import argparse
import json
import sys
from contextlib import ExitStack
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("spec", type=Path)
    ap.add_argument("--overlay", type=Path)
    ap.add_argument("scenarios", nargs="+", metavar="scenario:cell")
    args = ap.parse_args(argv)
    if args.overlay is not None:
        sys.path.insert(0, str(args.overlay / "src"))

    import jax

    from bench import run as harness
    from bench_helpers import faults

    dirs = ([args.overlay / "bench"] if args.overlay else []) + [ROOT / "bench"]
    bench = harness.Bench(args.spec, dirs)
    for item in args.scenarios:
        kind, cell = item.split(":")
        entry = bench.cell(cell)
        config = bench.config(entry["config"])
        out = {"scenario": kind, "cell": cell}
        if kind == "readings":
            drv = bench.module("drivers", config["driver"]).Driver(
                config, 11, jax.devices()[:entry["chips"]])
            out["readings"] = drv.control()
            out["limits"] = config["check"]
        else:
            with ExitStack() as stack:
                if kind not in ("sound", "traced"):
                    for p in faults(config["driver"], args.overlay).patches(kind):
                        stack.enter_context(p)
                res, counts = harness.run(bench, cell, 3_000_000_019, 0.3,
                                          kind == "traced", devices=jax.devices())
            out.update(correct=res["correct"], check=res["check"], **counts)
            if kind == "traced":
                out["metrics"] = res["metrics"]
            if kind == "sound":
                drv = bench.module("drivers", config["driver"]).Driver(
                    config, 11, jax.devices()[:entry["chips"]])
                out["work"] = drv.work()
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
