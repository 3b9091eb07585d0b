"""Plain reference of the matmul configurations: C = A @ B.

It imports nothing of the program. The operands are made here from the
seed, so the program and the reference multiply the same numbers; the
program gets them already sharded, the reference on one device.

The reference product runs on one device in blocks of rows at float32
``HIGHEST`` precision (six bf16 passes on a TPU), whose error is some 1e-6
of the result: far below the ~2e-3 of the configuration's product with
bf16-rounded operands. ``control`` is the same product with its operands
rounded to float8 e4m3's precision (3 mantissa bits), the precision below
bf16, and float32 accumulation.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS_PER_BLOCK = 1024


def operands(seed: int, m: int, k: int, n: int, sharding=None):
    """A (m, k) and B (k, n), standard normal float32, from ``seed``.

    Made on the device in one jitted call. The values do not depend on the
    sharding (JAX's partitionable threefry), so the reference can make them
    again on one device.
    """
    def make(key):
        ka, kb = jax.random.split(key)
        return (jax.random.normal(ka, (m, k), jnp.float32),
                jax.random.normal(kb, (k, n), jnp.float32))

    placed = {} if sharding is None else {"out_shardings": (sharding, sharding)}
    return jax.jit(make, **placed)(jax.random.key(seed))


@jax.jit
def _reference_rows(a_rows, b):
    return jnp.dot(a_rows, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def round_mantissa(x, bits: int = 3):
    """``x`` rounded to ``bits`` explicit mantissa bits (float8 e4m3's 3),
    by arithmetic: a chain of converts through float8 is one the TPU's
    compiler may drop, and then the control would be the program."""
    m, e = jnp.frexp(x)
    scale = 2.0 ** (bits + 1)
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


@jax.jit
def control_product(a_rows, b):
    """The control: the product of ``a_rows`` and ``b`` with both operands
    rounded to float8 e4m3's precision, accumulated in float32."""
    return jnp.dot(round_mantissa(a_rows).astype(jnp.bfloat16),
                   round_mantissa(b).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


@jax.jit
def _errors(c_rows, r_rows):
    """The largest row error ||C_i - R_i|| / ||R_i||, and the largest entry
    error |C_ij - R_ij| over its row's root mean square of R."""
    d2 = jnp.sum(jnp.square(c_rows - r_rows), axis=1)
    r2 = jnp.sum(jnp.square(r_rows), axis=1)
    rms = jnp.sqrt(r2 / r_rows.shape[1])
    entry = jnp.max(jnp.abs(c_rows - r_rows), axis=1) / rms
    return jnp.max(jnp.sqrt(d2 / r2)), jnp.max(entry)


def errors(outputs: list[np.ndarray], seed: int, m: int, k: int, n: int,
           device, control: bool = False) -> dict[str, float]:
    """``row_err`` and ``entry_err`` (see ``_errors``) of every row of every
    one of ``outputs`` (host arrays of the program's C) against the
    reference: the worst over all of them.

    With ``control`` the control product takes the outputs' place.
    """
    worst = {"row_err": 0.0, "entry_err": 0.0}
    with jax.default_device(device):
        a, b = operands(seed, m, k, n)
        for r0 in range(0, m, ROWS_PER_BLOCK):
            rows = a[r0:r0 + ROWS_PER_BLOCK]
            ref = _reference_rows(rows, b)
            cands = ([control_product(rows, b)] if control else
                     [jax.device_put(o[r0:r0 + ROWS_PER_BLOCK], device)
                      for o in outputs])
            for c in cands:
                for name, v in zip(worst, map(float, _errors(c, ref))):
                    worst[name] = max(worst[name], v) if np.isfinite(v) else math.inf
    return worst
