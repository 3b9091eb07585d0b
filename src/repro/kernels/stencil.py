"""2D 5-point stencil — Pallas TPU kernel (paper app 8's hot loop).

Halo handling without overlapping blocks: the same input array is passed
three times with row-block index maps (i-1, i, i+1) clamped at the grid
edges; the kernel assembles the 1-deep row halo in VMEM from the
neighbouring blocks' edge rows and edge-replicates columns in-register.
Grid is 1D over row tiles; full rows live in VMEM (row-major friendly),
so the row-block height ``bm`` is sized from the row width: the three
input blocks and the output block, each double-buffered, take at most
half of the 16 MiB of VMEM a v5e kernel may use by default, and the other
half holds the kernel's temporaries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Bytes the four double-buffered (bm, N) blocks may take together.
BLOCK_VMEM_BYTES = 8 * 1024 * 1024
_SUBLANES = 8


def rows_per_block(m: int, n: int, itemsize: int = 4) -> int:
    """The tallest row block that fits ``BLOCK_VMEM_BYTES``, divides
    ``m`` and is a multiple of 8 (or all of ``m``)."""
    cap = max(_SUBLANES, BLOCK_VMEM_BYTES // (8 * n * itemsize))
    for bm in range(min(cap, m), 0, -1):
        if m % bm == 0 and (bm % _SUBLANES == 0 or bm == m):
            return bm
    return m


def _stencil_kernel(prev_ref, cur_ref, next_ref, o_ref, *, n_i: int, bm: int):
    i = pl.program_id(0)
    cur = cur_ref[...]                                 # (bm, N)
    # Row halos from neighbouring blocks (edge-replicated at boundaries).
    top = jnp.where(i == 0, cur[0:1], prev_ref[bm - 1:bm])
    bot = jnp.where(i == n_i - 1, cur[bm - 1:bm], next_ref[0:1])
    f = jnp.concatenate([top, cur, bot], axis=0)       # (bm+2, N)
    # Column halos by edge replication (in-register shift).
    left = jnp.concatenate([f[:, 0:1], f[:, :-1]], axis=1)
    right = jnp.concatenate([f[:, 1:], f[:, -1:]], axis=1)
    out = 0.2 * (f + left + right
                 + jnp.concatenate([f[0:1], f[:-1]], axis=0)
                 + jnp.concatenate([f[1:], f[-1:]], axis=0))
    o_ref[...] = out[1:-1, :].astype(o_ref.dtype)


def stencil_pallas(field: jax.Array, *, bm: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """One Jacobi sweep of the 5-point stencil with edge-replicate BCs.
    ``bm`` defaults to :func:`rows_per_block` of the field's shape."""
    M, N = field.shape
    bm = (rows_per_block(M, N, field.dtype.itemsize) if bm is None
          else min(bm, M))
    assert M % bm == 0, (M, bm)
    n_i = M // bm
    kern = functools.partial(_stencil_kernel, n_i=n_i, bm=bm)

    def clamp(idx):
        return jnp.clip(idx, 0, n_i - 1)

    return pl.pallas_call(
        kern,
        grid=(n_i,),
        in_specs=[
            pl.BlockSpec((bm, N), lambda i: (clamp(i - 1), 0)),
            pl.BlockSpec((bm, N), lambda i: (i, 0)),
            pl.BlockSpec((bm, N), lambda i: (clamp(i + 1), 0)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), field.dtype),
        interpret=interpret,
    )(field, field, field)
