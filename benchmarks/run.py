"""Benchmark driver — one harness per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run           # everything
  PYTHONPATH=src python -m benchmarks.run --only loc_table
  PYTHONPATH=src python -m benchmarks.run --only mapper_tuning --only sim_eval

Prints a ``name,us_per_call,derived`` CSV at the end (microbench section)
plus the per-table reports above it. The ``mapper_tuning`` and
``sim_eval`` lanes write ``BENCH_tuning.json`` / ``BENCH_sim.json``
(uploaded as CI artifacts next to ``BENCH_mapping.json``).

Every run additionally aggregates the executed sections' results — each
harness's ``run()`` returns its machine-readable artifact — into one
top-level ``BENCH_perf.json`` trajectory file (machine info + per-section
timings + results), so the perf history of whatever ran is recorded per
PR instead of living only in scattered CI uploads. ``--perf-json ''``
disables it.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
from pathlib import Path

from benchmarks import (
    decompose_sweep,
    heuristic_gap,
    loc_table,
    mapper_tuning,
    mapping_eval,
    resilience_bench,
    serve_bench,
    sim_eval,
)

SECTIONS = {
    "loc_table": ("Table 1: mapper LoC, Mapple vs low-level", loc_table.run),
    "mapper_tuning": ("Table 2: mapper tuning headroom (autotuner search)",
                      mapper_tuning.run),
    "heuristic_gap": ("Heuristic gap: greedy baseline vs tuner optimum "
                      "(+ Fig 13 locality)", heuristic_gap.run),
    "decompose_sweep": ("Figs 14-17: decompose vs Algorithm 1 (180 configs)",
                        decompose_sweep.run),
    "mapping_eval": ("Mapping IR: vectorized vs per-point grid evaluation",
                     mapping_eval.run),
    "sim_eval": ("Simulator: time-domain tuning, engine parity/speedup, "
                 "1024-proc scale (+ BENCH_sim.json)", sim_eval.run),
    "serve_bench": ("Tuning service: cold vs warm trace replay + "
                    "warm-started search (+ BENCH_serve.json)",
                    serve_bench.run),
    "resilience_bench": ("Fault recovery: warm remap vs cold retune + "
                         "degraded-pricing parity (+ BENCH_resilience.json)",
                         resilience_bench.run),
}

PERF_JSON = "BENCH_perf.json"


def machine_info() -> dict:
    import os

    import jax

    return {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "processor": platform.processor(),
        "cpu_count": os.cpu_count(),
        "jax": {
            "version": jax.__version__,
            "backend": jax.default_backend(),
            "devices": [str(d) for d in jax.devices()],
        },
    }


def _trajectory(sections: dict) -> dict:
    """The headline number(s) of each executed section — the compact
    cross-PR comparison block at the top of ``BENCH_perf.json`` (diff
    this against the previous PR's instead of spelunking the full
    per-section results)."""
    headline: dict = {}
    for key, entry in sections.items():
        res = entry.get("result")
        row: dict = {"elapsed_s": round(entry.get("elapsed_s", 0.0), 3)}
        if key == "sim_eval" and isinstance(res, dict):
            eng = res.get("engine_bench") or {}
            jb = res.get("jax_bench") or {}
            jp = res.get("jax_parity") or {}
            par = res.get("engine_parity") or {}
            pb = res.get("pipeline_bench") or {}
            cb = res.get("cache_bench") or {}
            row.update({
                "batched_vs_event_speedup": eng.get("speedup"),
                "jax_vs_numpy_speedup": jb.get("speedup"),
                "pipeline_vs_sync_speedup": pb.get("speedup"),
                "warm_cache_speedup": cb.get("speedup"),
                "jax_parity_max_rel": jp.get("max_rel_diff"),
                "engine_parity_max_abs_s": par.get("max_abs_diff_s"),
                "mean_rank_agreement": res.get("mean_rank_agreement"),
            })
        elif key == "serve_bench" and isinstance(res, dict):
            rp = res.get("replay") or {}
            row.update({
                "warm_replay_speedup": rp.get("speedup"),
                "cold_p99_s": rp.get("cold_p99_s"),
                "warm_p99_s": rp.get("warm_p99_s"),
                "warm_start_ok": (res.get("warm_start") or {}).get("ok"),
            })
        elif key == "resilience_bench" and isinstance(res, dict):
            rm = res.get("remap") or {}
            pa = res.get("parity") or {}
            row.update({
                "warm_remap_speedup": rm.get("speedup"),
                "remap_quality_ok": (rm.get("placement_avoids_dead")
                                     and rm.get("not_worse_than_stale")),
                "degraded_parity_max_abs_s": pa.get("max_abs_diff_s"),
            })
        elif key == "mapping_eval" and isinstance(res, dict):
            row["speedup"] = res.get("speedup")
        elif key == "mapper_tuning" and isinstance(res, dict):
            row["all_oracles_rediscovered"] = res.get(
                "all_oracles_rediscovered")
        elif key == "microbench" and isinstance(res, list):
            row["us_per_call"] = {
                r["name"]: round(r["us_per_call"], 1) for r in res
            }
        headline[key] = {k: v for k, v in row.items() if v is not None}
    return headline


def write_perf_trajectory(sections: dict, path: str = PERF_JSON,
                          report=print) -> dict:
    """Aggregate executed sections into the per-PR perf trajectory file."""
    payload = {
        "machine": machine_info(),
        "trajectory": _trajectory(sections),
        "sections": sections,
    }
    Path(path).write_text(json.dumps(payload, indent=2, default=str) + "\n")
    report(f"\nwrote {path} ({len(sections)} section(s))")
    return payload


def microbench(report=print) -> list[tuple[str, float, str]]:
    """Core-op timings: name, us_per_call, derived."""
    import jax.numpy as jnp

    from repro.core import GPU, Machine, block_mapper
    from repro.core.decompose import optimal_factorization
    from repro.kernels import ops

    rows = []

    def timeit(name, fn, n=20, derived=""):
        fn()  # warmup
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn()
        if hasattr(out, "block_until_ready"):
            out.block_until_ready()
        us = (time.perf_counter() - t0) / n * 1e6
        rows.append((name, us, derived))

    timeit("decompose_solve_256x3",
           lambda: optimal_factorization(256, (8192, 8192, 64)),
           derived="optimal factorization; 3 dims")
    m = Machine(GPU, shape=(16, 16))
    mapper = block_mapper(m)
    timeit("mapper_eval_grid_16x16",
           lambda: mapper.assignment_grid((16, 16), use_cache=False),
           derived="256-point tile->device evaluation (vectorized, uncached)")
    timeit("mapper_eval_grid_16x16_cached",
           lambda: mapper.assignment_grid((16, 16)),
           derived="cache hit (the to_spmd steady state)")
    a = jnp.ones((256, 256), jnp.float32)
    b = jnp.ones((256, 256), jnp.float32)
    timeit("pallas_matmul_256_interp", lambda: ops.matmul(a, b), n=3,
           derived="interpret-mode (correctness path)")
    timeit("jnp_matmul_256", lambda: (a @ b), n=50,
           derived="XLA:CPU reference")
    f = jnp.ones((512, 512), jnp.float32)
    timeit("pallas_stencil_512_interp", lambda: ops.stencil_step(f), n=3,
           derived="interpret-mode")

    report("\nname,us_per_call,derived")
    for name, us, derived in rows:
        report(f"{name},{us:.1f},{derived}")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", action="append", default=None,
                    choices=list(SECTIONS),
                    help="run only the named section(s); repeatable")
    ap.add_argument("--perf-json", default=PERF_JSON,
                    help="aggregate trajectory output path ('' disables)")
    args = ap.parse_args(argv)
    keys = args.only if args.only else list(SECTIONS)
    results: dict = {}
    for key in keys:
        title, fn = SECTIONS[key]
        print(f"\n{'=' * 72}\n{title}\n{'=' * 72}")
        t0 = time.perf_counter()
        result = fn()
        results[key] = {
            "elapsed_s": time.perf_counter() - t0,
            "result": result,
        }
    if args.only is None:
        print(f"\n{'=' * 72}\nMicrobenchmarks\n{'=' * 72}")
        t0 = time.perf_counter()
        rows = microbench()
        results["microbench"] = {
            "elapsed_s": time.perf_counter() - t0,
            "result": [
                {"name": n, "us_per_call": us, "derived": d}
                for n, us, d in rows
            ],
        }
    if args.perf_json:
        write_perf_trajectory(results, args.perf_json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
