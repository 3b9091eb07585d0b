"""Readings that a cell's limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --first-seed <s> --seeds <n> --control <c>

For each of ``n`` seeds from ``s`` on, in one process: set up the cell's
driver as a run does, call the program's entry once, and compare its output
with the reference as a run does (the program's reading, the lower end of
each limit). For the first ``c`` of those seeds, read the control, the
reference computed in the precision below the configuration's, in the
program's place (the upper end). One JSON line per reading. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)

    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]
    from bench import run as harness

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    bench = harness.Bench(ROOT / "BENCHMARK.json", [BENCH])
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    try:
        devices = harness.accelerators(cell["chips"], bench.peaks)
    except harness.NoDevice as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    driver = bench.module("drivers", config["driver"]).Driver
    for i in range(args.seeds):
        seed = args.first_seed + i
        drv = driver(config, seed, devices[:cell["chips"]])
        checks = drv.check([jax.block_until_ready(drv.call())])
        print(json.dumps({"cell": args.workload, "seed": seed, "side": "program",
                          **{c["name"]: c["value"] for c in checks}}), flush=True)
        if i < args.control:
            print(json.dumps({"cell": args.workload, "seed": seed,
                              "side": "control", **drv.control()}), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
