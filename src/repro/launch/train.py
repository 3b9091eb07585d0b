"""Training launcher: --arch <id> [--steps N] [--scale reduced|full].

``--scale reduced`` trains a tiny same-family config (quick host checks);
``--scale full`` trains the published widths on the default device:

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --batch 16 --seq 64 --ckpt-dir ckpt
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --scale full --steps 3 --batch 4 --seq 2048

The last line printed is a JSON summary (losses, steps, one-off wall time),
which ``main`` also returns.
"""
from __future__ import annotations

import argparse
import json
import time


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.train", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--scale", default="reduced", choices=["reduced", "full"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--backpressure", type=int, default=2,
                    help="max in-flight steps (the Backpressure directive)")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a simulated failure at this step (demo)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.data import make_pipeline
    from repro.models import build
    from repro.runtime import FailureInjector, SimulatedFailure
    from repro.training import (
        AdamWConfig, TrainLoop, TrainState, init_state, make_train_step,
    )

    cfg = get_config(args.arch)
    if args.scale == "reduced":
        cfg = cfg.reduced()
    model = build(cfg)
    print(f"arch={args.arch} scale={args.scale} params={model.n_params:,}")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                          total_steps=args.steps)
    pipe = make_pipeline(cfg, seq_len=args.seq, global_batch=args.batch,
                         seed=args.seed)
    step_fn = jax.jit(make_train_step(model, opt_cfg,
                                      compress_grads=args.compress_grads))

    mgr = None
    start = 0
    state = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3, async_save=True)
        if args.resume and mgr.latest_step() is not None:
            start, tree, extra = mgr.restore()
            state = TrainState.from_tree(tree)
            print(f"resumed from step {start}")
    if state is None:
        state = init_state(model, jax.random.key(args.seed), opt_cfg,
                           compress_grads=args.compress_grads)

    injector = (
        FailureInjector(fail_at_steps=(args.fail_at,), max_failures=1)
        if args.fail_at is not None else None
    )
    loop = TrainLoop(step_fn, pipe, backpressure=args.backpressure,
                     checkpoint_manager=mgr, save_every=args.save_every)
    t0 = time.time()
    if injector is None:
        state, hist = loop.run(state, start, args.steps)
    else:
        # Demonstrate checkpoint/restart under an injected failure.
        try:
            def guarded(step, st):
                injector.check(step)
                return step_fn(st, pipe.batch(step))

            guarded_loop = TrainLoop(guarded, pipe,
                                     backpressure=args.backpressure,
                                     checkpoint_manager=mgr,
                                     save_every=args.save_every)
            state, hist = guarded_loop.run(state, start, args.steps)
        except SimulatedFailure as e:
            print(f"!! {e}; restarting from latest checkpoint")
            assert mgr is not None, "--fail-at needs --ckpt-dir"
            mgr.wait()
            start, tree, _ = mgr.restore()
            state = TrainState.from_tree(tree)
            state, hist = loop.run(state, start, args.steps)
    dt = time.time() - t0
    if mgr is not None:
        mgr.wait()
    summary = {
        "arch": args.arch, "scale": args.scale,
        "device": jax.devices()[0].device_kind,
        "losses": [h["loss"] for h in hist],
        "first_loss": hist[0]["loss"], "last_loss": hist[-1]["loss"],
        "steps": len(hist), "wall_s": round(dt, 1),
        "steps_per_s": round(len(hist) / dt, 2),
    }
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
