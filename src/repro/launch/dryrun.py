"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh must compile for every
assigned architecture and input shape, and the compiled artifacts yield
the memory/cost/collective numbers EXPERIMENTS.md reports.

The meshes need 512 devices; on a host, ask XLA for that many fake CPU
devices before JAX starts:

  export JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=512
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.json
"""
import argparse
import json
import time
import traceback
from pathlib import Path


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             mode: str | None = None, seq_shard: bool = True,
             verbose: bool = True, knobs=None) -> dict:
    import jax

    from repro.configs import get_config
    from repro.launch import hlo_cost, roofline
    from repro.launch import knobs as knobs_mod
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import runnable
    from repro.launch.steps import make_cell
    from repro.models.config import SHAPES

    if knobs is None:
        knobs = knobs_mod.Knobs()

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = runnable(cfg, shape)
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "skipped" if not ok else "pending",
    }
    if not ok:
        record["reason"] = why
        if verbose:
            print(f"[skip] {arch} x {shape_name} x {mesh_name}: {why}")
        return record

    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_chips = mesh.devices.size
    t0 = time.time()
    try:
        with mesh, knobs_mod.apply(knobs):
            cell = make_cell(arch, cfg, shape, mesh, mode=mode,
                             seq_shard=seq_shard)
            lowered = cell.lower()
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost_list = compiled.cost_analysis()
            xla_cost = cost_list if isinstance(cost_list, dict) else (
                cost_list[0] if cost_list else {}
            )
            hlo = compiled.as_text()
        # Loop-aware recount (XLA's cost_analysis counts while bodies once).
        costs = hlo_cost.analyze(hlo)
        cost = {"flops": costs.flops, "bytes accessed": costs.bytes}
        rt = roofline.terms(
            arch, shape, cfg, mesh_name, n_chips, cost, costs.collective_bytes
        )
        record.update(
            status="ok",
            compile_s=round(time.time() - t0, 1),
            n_chips=n_chips,
            memory_analysis=_mem_dict(mem),
            flops=rt.hlo_flops,
            bytes_accessed=rt.hlo_bytes,
            collective_bytes=costs.collective_bytes,
            collectives={"bytes": costs.collective_by_kind},
            xla_cost_analysis={
                "flops": float(xla_cost.get("flops", 0.0)),
                "bytes accessed": float(xla_cost.get("bytes accessed", 0.0)),
            },
            roofline={
                "compute_s": rt.compute_s,
                "memory_s": rt.memory_s,
                "collective_s": rt.collective_s,
                "bottleneck": rt.bottleneck,
                "model_flops": rt.model_flops,
                "useful_flops_ratio": rt.flops_ratio,
            },
            sharding_mode=cell.plan.mode,
        )
        if verbose:
            print(f"[ok]   {arch} x {shape_name} x {mesh_name} "
                  f"({record['compile_s']}s, mode={cell.plan.mode})")
            print(f"       memory: {record['memory_analysis']}")
            print(f"       cost: flops={rt.hlo_flops:.3e} "
                  f"bytes={rt.hlo_bytes:.3e} "
                  f"coll={costs.collective_bytes / 2**20:.1f}MiB")
            print(f"       roofline: compute={rt.compute_s:.3e}s "
                  f"memory={rt.memory_s:.3e}s coll={rt.collective_s:.3e}s "
                  f"-> {rt.bottleneck}-bound, useful={rt.flops_ratio:.2f}")
    except Exception as e:  # noqa: BLE001 - report, continue the sweep
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[ERR]  {arch} x {shape_name} x {mesh_name}: {e}")
    return record


def _mem_dict(mem) -> dict:
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out:
        out["repr"] = str(mem)[:500]
    return out


def main() -> None:
    from repro.configs import ARCH_IDS
    from repro.models.config import SHAPES

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (see repro.configs)")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all' (train_4k, prefill_32k, "
                         "decode_32k, long_500k)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--mode", default=None, choices=[None, "tp", "fsdp"],
                    help="override the sharding-policy mode")
    ap.add_argument("--no-seq-shard", action="store_true",
                    help="disable sequence-parallel residual sharding")
    ap.add_argument("--baseline", action="store_true",
                    help="paper-faithful baseline knobs (scan WKV, no "
                         "shard_map SP attention, no microbatching)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()

    from repro.launch.knobs import Knobs

    knobs = (
        Knobs(wkv_impl="scan", sp_attention=False, microbatch=1)
        if args.baseline else Knobs(wkv_impl="chunked")
    )

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    records = []
    t0 = time.time()
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                records.append(
                    run_cell(arch, shape, mesh_name, mode=args.mode,
                             seq_shard=not args.no_seq_shard, knobs=knobs)
                )
    ok = sum(r["status"] == "ok" for r in records)
    skip = sum(r["status"] == "skipped" for r in records)
    err = sum(r["status"] == "error" for r in records)
    print(f"\n=== dry-run: {ok} ok, {skip} skipped, {err} errors, "
          f"{time.time() - t0:.0f}s total ===")
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(records, indent=1))
        print(f"wrote {args.out}")
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
