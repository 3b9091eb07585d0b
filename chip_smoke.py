"""Drive the system's three main paths once on a TPU, in one process.

    python3 chip_smoke.py               # one chip: every phase below
    python3 chip_smoke.py --four-chips  # a v5e:2x2 host: phase 1 at procs=4

Phases, each through the entry point a user calls:

1. The nine mapped programs at the registry's problem sizes
   (``repro.apps.run --all --execute --procs 1``), each compared with its
   single-device reference under the bound ``repro.apps.validate`` states.
   With ``--four-chips`` only this phase runs, at ``--procs 4``: the eight
   apps whose grid policy admits 4 (johnson needs a cubic count), each on
   four distinct devices.
2. The device pricer: a time-domain tune of the registry
   (``repro.apps.run --all --tune --time --backend jax``), the registry-wide
   parity of the JAX engine with the NumPy engine (``benchmarks.sim_eval
   .jax_parity``, <= 1e-6 relative), and the tuning service answering a
   seeded 8-request demo trace (``repro.serving.serve --demo 8 --backend
   jax``).
3. smollm-135m at its published widths: 3 training steps at batch 4 x 2048
   tokens (``repro.launch.train``) and 4 requests of 128 prompt and 32
   generated tokens (``repro.launch.serve``). Weights are random, from a seed.

A chip belongs to one process at a time, so nothing here starts a child
process. Each check prints one line: the device, its error or parity against
the bound, and its one-off wall time, compilation included (not a benchmark).
What an entry point prints itself is kept back and shown only when its phase
fails. The script exits non-zero when JAX finds no TPU or any phase fails;
otherwise its last line is ``{"ok": true, "device": {...}}`` as JAX reports
the device.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import sys
import tempfile
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent

#: Relative parity of the device pricer with the NumPy engine.
PRICER_RTOL = 1e-6
#: smollm-135m at its published widths and depth (30 layers).
MODEL = "smollm-135m"
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 2048
REQUESTS, PROMPT_TOKENS, GEN_TOKENS = 4, 128, 32
DEMO_REQUESTS = 8


class PhaseFailed(RuntimeError):
    pass


def _quiet(fn, *args):
    """Call ``fn`` with its stdout captured; the capture is returned with
    the result and printed by the caller only when the phase fails."""
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            return fn(*args), buf.getvalue()
    except Exception as e:
        raise PhaseFailed(f"{e}\n--- output ---\n{buf.getvalue()[-4000:]}") \
            from e


def _line(report, phase: str, device: str, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    report(f"[{phase}] device={device} {parts}")


def apps_phase(report, procs: int, expect: int) -> None:
    """Every registry app's kernel at ``procs`` against its reference."""
    from repro.apps import run as apprun

    with tempfile.TemporaryDirectory() as tmp:
        out = Path(tmp) / "execute.json"
        rc, log = _quiet(apprun.main, ["--all", "--execute", "--procs",
                                       str(procs), "--json", str(out)])
        rows = json.loads(out.read_text())["apps"] if out.exists() else []
    for r in rows:
        _line(report, f"app {r['app']}", r["device"], procs=r["procs"],
              grid="x".join(map(str, r["grid"])),
              distinct_devices=r["distinct_devices"],
              max_err=f"{r['max_err']:.3e}",
              bound=f"{r['bound']:g}({r['err_kind']})", ok=r["ok"],
              wall_s=f"{r['wall_s']:.2f}")
    bad = [r["app"] for r in rows
           if not r["ok"] or r["distinct_devices"] != procs]
    if rc != 0 or len(rows) != expect or bad:
        raise PhaseFailed(f"apps at procs={procs}: rc={rc}, {len(rows)} of "
                          f"{expect} checked, failed {bad}\n{log[-4000:]}")


def pricer_phase(report, device: str) -> None:
    """Tune, parity and the service, all pricing on the chip."""
    from benchmarks import sim_eval
    from repro.apps import run as apprun
    from repro.serving import serve as svc

    t0 = time.perf_counter()
    rc, log = _quiet(apprun.main, ["--all", "--tune", "--time",
                                   "--backend", "jax"])
    tuned = [ln for ln in log.splitlines() if ln.startswith("tuned ")]
    _line(report, "pricer tune", device, rc=rc,
          summary=repr(tuned[-1] if tuned else None),
          wall_s=f"{time.perf_counter() - t0:.2f}")
    if rc != 0 or not tuned:
        raise PhaseFailed(f"tune --time --backend jax: rc={rc}\n{log[-4000:]}")

    t0 = time.perf_counter()
    par, _ = _quiet(sim_eval.jax_parity)
    _line(report, "pricer parity", device, dtype="float64",
          placements=par["placements"],
          max_rel=f"{par['max_rel_diff']:.3e}", bound=f"{PRICER_RTOL:g}",
          ok=par["ok"], wall_s=f"{time.perf_counter() - t0:.2f}")
    if not (par["ok"] and par["max_rel_diff"] <= PRICER_RTOL):
        raise PhaseFailed(f"pricer parity {par}")

    with tempfile.TemporaryDirectory() as tmp:
        stats_path = Path(tmp) / "stats.json"
        t0 = time.perf_counter()
        rc, log = _quiet(svc.main, ["--demo", str(DEMO_REQUESTS),
                                    "--backend", "jax",
                                    "--stats-json", str(stats_path)])
        stats = json.loads(stats_path.read_text())
    _line(report, "pricer service", device, rc=rc,
          completed=f"{stats['completed']}/{DEMO_REQUESTS}",
          rejected=stats["rejected"], searches=stats["searches"],
          cache_hits=stats["cache_hits"],
          wall_s=f"{time.perf_counter() - t0:.2f}")
    if rc != 0 or stats["completed"] != DEMO_REQUESTS:
        raise PhaseFailed(f"service demo: rc={rc}\n{log[-4000:]}")


def model_phase(report, scale: str = "full") -> None:
    """Train a few steps and serve a few requests of the model."""
    from repro.launch import serve, train

    common = ["--arch", MODEL, "--scale", scale]
    t0 = time.perf_counter()
    tr, log = _quiet(train.main, common + [
        "--steps", str(TRAIN_STEPS), "--batch", str(TRAIN_BATCH),
        "--seq", str(TRAIN_SEQ)])
    losses = tr["losses"]
    _line(report, "model train", tr["device"], arch=MODEL, scale=scale,
          losses=[round(x, 4) for x in losses],
          wall_s=f"{time.perf_counter() - t0:.2f}")
    if (len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses))
            or not losses[-1] < losses[0]):
        raise PhaseFailed(f"training losses {losses}\n{log[-4000:]}")

    t0 = time.perf_counter()
    sv, log = _quiet(serve.main, common + [
        "--batch", str(REQUESTS), "--prompt-len", str(PROMPT_TOKENS),
        "--gen", str(GEN_TOKENS)])
    _line(report, "model serve", sv["device"], arch=MODEL, scale=scale,
          requests=sv["requests"], prompt_tokens=sv["prompt_tokens"],
          generated_tokens=sv["generated_tokens"],
          logits_finite=sv["logits_finite"],
          wall_s=f"{time.perf_counter() - t0:.2f}")
    if (sv["generated_tokens"] != REQUESTS * GEN_TOKENS
            or not sv["logits_finite"]):
        raise PhaseFailed(f"serving: {sv}\n{log[-4000:]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mapped apps at procs=4 on a v5e:2x2 "
                         "host, against the single-device reference")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    chips = 4 if args.four_chips else 1
    if len(devices) < chips:
        print(f"chip_smoke: needs {chips} chips, JAX has {len(devices)}",
              file=sys.stderr)
        return 2

    # Run this checkout's code, never an installed copy of it.
    for path in (REPO, REPO / "src"):
        sys.path.insert(0, str(path))
    from repro.runtime import compile_cache

    if Path(compile_cache.__file__).resolve().parents[3] != REPO:
        print(f"chip_smoke: imported {compile_cache.__file__}, which is not "
              f"under {REPO}", file=sys.stderr)
        return 2
    compile_cache.enable_compile_cache()

    def report(msg: str) -> None:
        print(msg, flush=True)

    if args.four_chips:
        phases = [("apps procs=4", lambda: apps_phase(report, 4, 8))]
    else:
        phases = [("apps procs=1", lambda: apps_phase(report, 1, 9)),
                  ("pricer", lambda: pricer_phase(report, dev.device_kind)),
                  ("model", lambda: model_phase(report))]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:  # noqa: BLE001 - reported; the run exits non-zero
            failed.append(name)
            traceback.print_exc()
        report(f"[phase {name}] {'FAILED' if name in failed else 'ok'} "
               f"wall_s={time.perf_counter() - t0:.2f}")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
