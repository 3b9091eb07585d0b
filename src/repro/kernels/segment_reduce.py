"""Blocked segment-reduce kernel for congestion tables.

The simulator's JAX pricing backend (``repro.sim.jax_backend``) reduces
dense per-candidate congestion tables ``vals[row, col]`` — one row per
(candidate, slab) pair, one column per processor — in two shapes:

  * ``seg == 1``: per-row **max** (the stride-1 level, where every port
    carries at most one message per slab and direction);
  * ``seg == level stride``: per-row max of contiguous **segment sums**
    (the outer levels, where the ``seg`` processors of one subtree share
    the subtree's port and their byte loads add before the max).

Both are one kernel: ``out[r] = max_j sum_{i<seg} vals[r, j*seg + i]``.

Tiling: grid (rows/br, cols/bc) with the column axis fastest; each block
reduces its (br, bc) tile to per-row partial maxima accumulated in VMEM
across the column sweep. The tiling follows the TPU's (8, 128) rule: ``br``
is a multiple of 8, ``bc`` a multiple of both 128 and ``seg`` (so no
segment straddles a block boundary), and the per-row result is kept
lane-dense as a (br, 128) block whose lanes all hold the row's value.
Segment sums are formed in-register by lane rotations (``log2(seg)`` of
them), and only the lanes that start a segment enter the max. Values are
assumed non-negative (they are message counts and byte loads): the
wrapper zero-pads ragged shapes, and a zero pad segment is exactly an
idle port. Mosaic has no float64, so the kernel takes float32 on the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BR = 8
DEFAULT_BC = 512
_SUBLANES = 8
_LANES = 128


def _window_sums(blk: jax.Array, seg: int) -> jax.Array:
    """``out[:, c] = sum_{i<seg} blk[:, c + i]`` (lanes wrap; the lanes
    that start an aligned segment never read a wrapped lane)."""
    bc = blk.shape[1]

    def ahead(x, k):                     # x[:, c + k]
        return pltpu.roll(x, bc - k, 1)

    # ``span`` holds sums of ``width`` consecutive lanes; the binary digits
    # of ``seg`` pick which spans, at which offsets, make up the window.
    out, span, width, off, rest = None, blk, 1, 0, seg
    while rest:
        if rest & 1:
            term = span if off == 0 else ahead(span, off)
            out = term if out is None else out + term
            off += width
        rest >>= 1
        if rest:
            span = span + ahead(span, width)
            width *= 2
    return out


def _segment_rowmax_kernel(v_ref, o_ref, acc_ref, *, seg: int, n_c: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = v_ref[...]
    if seg > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
        blk = jnp.where(lane % seg == 0, _window_sums(blk, seg), 0)
    part = blk.max(axis=1, keepdims=True)               # (br, 1)
    acc_ref[...] = jnp.maximum(acc_ref[...], part)

    @pl.when(j == n_c - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def segment_rowmax_pallas(
    vals: jax.Array,
    seg: int = 1,
    *,
    br: int = DEFAULT_BR,
    bc: int = DEFAULT_BC,
    interpret: bool = False,
) -> jax.Array:
    """``max_j sum_{i<seg} vals[r, j*seg + i]`` per row, for ``vals >= 0``.

    ``br``/``bc`` are rounded to the tiling the TPU accepts (see the
    module docstring), and the table is zero-padded up to it (a zero pad
    segment behaves as an idle port under the non-negative contract).
    """
    rows, cols = vals.shape
    seg = int(seg)
    assert seg >= 1 and cols % seg == 0, (vals.shape, seg)
    if vals.dtype == jnp.float64 and not interpret:
        raise ValueError("segment_rowmax on the TPU takes float32: Mosaic "
                         "has no float64")
    unit = math.lcm(seg, _LANES)
    bc = unit * max(1, min(bc, cols) // unit)
    br = _SUBLANES * max(1, br // _SUBLANES)
    pad_r = -rows % br
    pad_c = -cols % bc
    if pad_r or pad_c:
        vals = jnp.pad(vals, ((0, pad_r), (0, pad_c)))
    grid = (vals.shape[0] // br, vals.shape[1] // bc)
    out = pl.pallas_call(
        functools.partial(_segment_rowmax_kernel, seg=seg, n_c=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, _LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((vals.shape[0], _LANES), vals.dtype),
        scratch_shapes=[pltpu.VMEM((br, _LANES), vals.dtype)],
        interpret=interpret,
    )(vals)
    return out[:rows, 0]
