"""WKV6 recurrence (RWKV-6 "Finch") — Pallas TPU kernel.

    y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

Adaptation note (DESIGN.md): the reference CUDA kernel assigns one thread
per channel with shared-memory staging. On TPU the natural decomposition is
one grid step per (batch*head, time-chunk): the (N, N) state matrix lives
in VMEM scratch and persists across the sequential time-chunk axis; inside
a chunk a fori_loop applies the rank-1 updates with VPU outer products.
Time stays sequential (the recurrence is inherently so); parallelism comes
from the (batch*head) grid axis — on real TPUs, from Megacore + multiple
chips via shard_map over heads.

Layout: r/k/v/w (BH, T, N) fp32; u (BH, N); outputs y (BH, T, N) and the
final state (BH, N, N). ``u`` enters the kernel as (BH, 1, N) so that its
(1, 1, N) block spans the array's last two dimensions, as the TPU's
(8, 128) block rule requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 128


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref, s_ref,
                 *, bt: int, n_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0, 0]                                     # (N,)

    def step(t, _):
        r_t = r_ref[0, t]                               # (N,)
        k_t = k_ref[0, t]
        v_t = v_ref[0, t]
        w_t = w_ref[0, t]
        kv = k_t[:, None] * v_t[None, :]                # (N, N) rank-1
        s = s_ref[...]
        y = ((s + u[:, None] * kv) * r_t[:, None]).sum(axis=0)
        y_ref[0, t] = y.astype(y_ref.dtype)
        s_ref[...] = w_t[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(ti == n_t - 1)
    def _flush():
        sout_ref[0] = s_ref[...].astype(sout_ref.dtype)


def wkv6_pallas(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array,
    *, bt: int = DEFAULT_BT, interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (BH,T,N), final_state (BH,N,N)). Zero initial state."""
    BH, T, N = r.shape
    bt = min(bt, T)
    assert T % bt == 0, (T, bt)
    grid = (BH, T // bt)
    kern = functools.partial(_wkv6_kernel, bt=bt, n_t=grid[1])
    y, s_out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, N), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, N, N), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, N), r.dtype),
            jax.ShapeDtypeStruct((BH, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(BH, 1, N))
    return y, s_out
