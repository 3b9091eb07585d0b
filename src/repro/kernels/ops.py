"""Jit'd public wrappers around the Pallas kernels.

On the CPU backend the kernels execute in interpret mode — the kernel body
runs in Python for correctness validation; on every other backend they
are compiled (on TPU they lower to Mosaic). Model code calls these
through ``use_pallas=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa_mod
from repro.kernels import mamba_scan as ms_mod
from repro.kernels import matmul as mm_mod
from repro.kernels import segment_reduce as sr_mod
from repro.kernels import stencil as st_mod
from repro.kernels import wkv6 as wkv_mod


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def matmul(a, b, bm: int = mm_mod.DEFAULT_BM, bn: int = mm_mod.DEFAULT_BN,
           bk: int = mm_mod.DEFAULT_BK):
    return mm_mod.matmul_pallas(a, b, bm=bm, bn=bn, bk=bk,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "scale", "causal"))
def flash_attention(q, k, v, *, window: int = 0, scale=None,
                    causal: bool = True):
    """Model-layout wrapper: q (B,S,H,hd), k/v (B,S,Kv,hd) -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    Kv = k.shape[2]
    if Kv != H:
        k = jnp.repeat(k, H // Kv, axis=2)
        v = jnp.repeat(v, H // Kv, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    out = fa_mod.flash_attention_pallas(
        qf, kf, vf, window=window, scale=scale, causal=causal,
        interpret=_interpret(),
    )
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("bm",))
def stencil_step(field, bm: int | None = None):
    return st_mod.stencil_pallas(field, bm=bm, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("seg", "br", "bc"))
def segment_rowmax(vals, seg: int = 1, br: int = sr_mod.DEFAULT_BR,
                   bc: int = sr_mod.DEFAULT_BC):
    """Per-row max of length-``seg`` segment sums (congestion reduce)."""
    return sr_mod.segment_rowmax_pallas(vals, seg, br=br, bc=bc,
                                        interpret=_interpret())


@jax.jit
def wkv6(r, k, v, w, u, state=None):
    """Model-layout wrapper: r/k/v/w (B,S,H,N), u (H,N), state (B,H,N,N).

    Contract: the fused kernel assumes a ZERO initial state (the training
    path always starts from zeros). The decode path (non-zero state, single
    step) uses the scan reference in repro.models.rwkv6 instead.
    """
    B, S, H, N = r.shape
    to_flat = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    uf = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, N)
    y, s = wkv_mod.wkv6_pallas(
        to_flat(r), to_flat(k), to_flat(v), to_flat(w), uf,
        interpret=_interpret(),
    )
    y = y.reshape(B, H, S, N).transpose(0, 2, 1, 3)
    return y, s.reshape(B, H, N, N)


@jax.jit
def mamba_scan(xs, dt, Bs, Cs, A):
    """Selective scan (zero initial state); see kernels/mamba_scan.py."""
    return ms_mod.mamba_scan_pallas(xs, dt, Bs, Cs, A,
                                    interpret=_interpret())
