"""Serving launcher: prefill + batched decode with a KV/state cache.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --scale full --batch 4 --prompt-len 128 --gen 32

Each of the ``--batch`` rows is one request. ``--scale reduced`` serves a
tiny same-family config (quick host checks); ``--scale full`` the
published widths. The last line printed is a JSON summary (generated
tokens, one-off wall times), which ``main`` also returns.
"""
from __future__ import annotations

import argparse
import json
import time


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.serve", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--scale", default="reduced", choices=["reduced", "full"])
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import get_config
    from repro.models import build

    cfg = get_config(args.arch)
    if args.scale == "reduced":
        cfg = cfg.reduced()
    model = build(cfg)
    key = jax.random.key(args.seed)
    params = model.init(key)
    B = args.batch
    max_len = args.prompt_len + args.gen

    if cfg.stub_frontend:
        prompt = 0.02 * jax.random.normal(
            key, (B, args.prompt_len, cfg.d_model), jnp.float32
        )
    else:
        prompt = jax.random.randint(
            key, (B, args.prompt_len), 0, cfg.vocab_size
        )

    # --- prefill: teacher-force the prompt through decode steps to build
    # the cache (single-token path keeps one code path for all families).
    decode = jax.jit(model.decode_step)
    cache = model.init_cache(B, max_len)
    t0 = time.time()
    logits = None
    for t in range(args.prompt_len):
        tok = prompt[:, t:t + 1]
        logits, cache = decode(params, cache, jnp.int32(t), tok)
    jax.block_until_ready(logits)
    prefill_s = time.time() - t0

    # --- batched greedy/temperature decode
    outs = []
    t0 = time.time()
    sample_key = jax.random.key(args.seed + 1)
    for t in range(args.prompt_len, max_len):
        flat = logits.reshape(B, -1)
        if args.temperature > 0:
            sample_key, sub = jax.random.split(sample_key)
            nxt = jax.random.categorical(sub, flat / args.temperature, axis=-1)
        else:
            nxt = jnp.argmax(flat, axis=-1)
        nxt = jnp.clip(nxt, 0, cfg.vocab_size - 1).astype(jnp.int32)
        outs.append(nxt)
        if cfg.stub_frontend:
            tok = 0.02 * jax.random.normal(
                jax.random.key(t), (B, 1, cfg.d_model), jnp.float32
            )
        else:
            tok = nxt[:, None]
        logits, cache = decode(params, cache, jnp.int32(t), tok)
    jax.block_until_ready(logits)
    decode_s = time.time() - t0

    tokens = jnp.stack(outs, axis=1)
    print("generated token ids (first row):", tokens[0].tolist())
    summary = {
        "arch": args.arch, "scale": args.scale,
        "device": jax.devices()[0].device_kind,
        "requests": B,
        "prompt_tokens": B * args.prompt_len,
        "generated_tokens": int(tokens.size),
        "logits_finite": bool(jnp.isfinite(logits).all()),
        "prefill_s": round(prefill_s, 3),
        "decode_s": round(decode_s, 3),
        "decode_tok_per_s": round(B * args.gen / max(decode_s, 1e-9), 1),
    }
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
