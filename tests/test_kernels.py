"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles.

All kernels run in interpret mode on CPU (the TPU lowering is exercised by
the same pallas_call on real hardware).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.stencil import stencil_pallas
from repro.kernels.wkv6 import wkv6_pallas


# fp32 tolerance covers blocked-vs-flat accumulation order at k ~ 512.
TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _assert_close(out, expect, dtype):
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **TOL[dtype],
    )


# ------------------------------------------------------------------- matmul
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (128, 512, 256), (384, 128, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(m, k, n, dtype):
    a = jax.random.normal(jax.random.key(0), (m, k)).astype(dtype)
    b = jax.random.normal(jax.random.key(1), (k, n)).astype(dtype)
    out = matmul_pallas(a, b, interpret=True)
    _assert_close(out, ref.matmul(a, b), dtype)


@pytest.mark.parametrize("bm,bn,bk", [(64, 64, 64), (128, 128, 64),
                                      (64, 128, 128)])
def test_matmul_block_sweep(bm, bn, bk):
    a = jax.random.normal(jax.random.key(2), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.key(3), (256, 256), jnp.float32)
    out = matmul_pallas(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    _assert_close(out, ref.matmul(a, b), jnp.float32)


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("s,d", [(128, 64), (256, 64), (256, 128)])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_shapes(s, d, window):
    BH = 4
    q = jax.random.normal(jax.random.key(0), (BH, s, d), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (BH, s, d), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (BH, s, d), jnp.float32)
    out = flash_attention_pallas(q, k, v, window=window, bq=64, bk=64,
                                 interpret=True)
    expect = ref.flash_attention(q, k, v, window=window)
    _assert_close(out, expect, jnp.float32)


def test_flash_attention_bf16():
    BH, s, d = 2, 128, 64
    q = jax.random.normal(jax.random.key(0), (BH, s, d)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (BH, s, d)).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (BH, s, d)).astype(jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, bq=64, bk=64, interpret=True)
    expect = ref.flash_attention(q, k, v)
    _assert_close(out, expect, jnp.bfloat16)


def test_flash_attention_gqa_wrapper():
    B, S, H, Kv, hd = 2, 128, 4, 2, 32
    q = jax.random.normal(jax.random.key(0), (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (B, S, Kv, hd), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (B, S, Kv, hd), jnp.float32)
    out = ops.flash_attention(q, k, v)
    from repro.models import layers

    expect = layers.naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=3e-5, atol=3e-5)


@settings(max_examples=8, deadline=None)
@given(
    nq=st.sampled_from([64, 128]),
    window=st.sampled_from([0, 32, 128]),
    seed=st.integers(0, 5),
)
def test_flash_attention_property(nq, window, seed):
    BH, d = 2, 32
    q = jax.random.normal(jax.random.key(seed), (BH, nq, d), jnp.float32)
    k = jax.random.normal(jax.random.key(seed + 1), (BH, nq, d), jnp.float32)
    v = jax.random.normal(jax.random.key(seed + 2), (BH, nq, d), jnp.float32)
    out = flash_attention_pallas(q, k, v, window=window, bq=32, bk=32,
                                 interpret=True)
    expect = ref.flash_attention(q, k, v, window=window)
    _assert_close(out, expect, jnp.float32)


# ------------------------------------------------------------------ stencil
@pytest.mark.parametrize("m,n,bm", [(128, 128, 64), (256, 128, 128),
                                    (192, 256, 64), (96, 128, None)])
def test_stencil_shapes(m, n, bm):
    f = jax.random.normal(jax.random.key(0), (m, n), jnp.float32)
    out = stencil_pallas(f, bm=bm, interpret=True)
    _assert_close(out, ref.stencil(f), jnp.float32)


@pytest.mark.parametrize("m,n", [(1024, 8192), (2048, 16384), (96, 128),
                                 (12, 4096)])
def test_stencil_rows_per_block_fits_vmem(m, n):
    """The default row block divides the field, keeps the (8, 128) rule,
    and its four double-buffered blocks fit the VMEM budget."""
    from repro.kernels.stencil import BLOCK_VMEM_BYTES, rows_per_block

    bm = rows_per_block(m, n)
    assert m % bm == 0 and (bm % 8 == 0 or bm == m)
    assert 4 * 2 * bm * n * 4 <= BLOCK_VMEM_BYTES


def test_stencil_matches_science_app_reference():
    from repro.science import stencil2d

    cfg = stencil2d.StencilConfig(nx=128, ny=128, steps=1)
    f = jax.random.normal(jax.random.key(1), (128, 128), jnp.float32)
    out = stencil_pallas(f, bm=64, interpret=True)
    expect = stencil2d.reference(f, cfg)
    _assert_close(out, expect, jnp.float32)


# --------------------------------------------------------------------- wkv6
@pytest.mark.parametrize("t,n,bt", [(64, 16, 32), (128, 32, 64),
                                    (128, 64, 128)])
def test_wkv6_shapes(t, n, bt):
    BH = 3
    key = jax.random.key(0)
    r = jax.random.normal(key, (BH, t, n), jnp.float32) * 0.5
    k = jax.random.normal(jax.random.key(1), (BH, t, n), jnp.float32) * 0.5
    v = jax.random.normal(jax.random.key(2), (BH, t, n), jnp.float32) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(jax.random.key(3), (BH, t, n))) * 0.5 + 0.4
    u = jax.random.normal(jax.random.key(4), (BH, n), jnp.float32) * 0.1
    y, s = wkv6_pallas(r, k, v, w, u, bt=bt, interpret=True)
    ye, se = ref.wkv6(r, k, v, w, u)
    _assert_close(y, ye, jnp.float32)
    _assert_close(s, se, jnp.float32)


def test_wkv6_chunking_invariance():
    """Same result regardless of time-chunk size (state carry correct)."""
    BH, t, n = 2, 128, 16
    key = jax.random.key(7)
    r = jax.random.normal(key, (BH, t, n), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.key(8), (BH, t, n), jnp.float32) * 0.3
    v = jax.random.normal(jax.random.key(9), (BH, t, n), jnp.float32) * 0.3
    w = jnp.full((BH, t, n), 0.9, jnp.float32)
    u = jnp.full((BH, n), 0.05, jnp.float32)
    y32, s32 = wkv6_pallas(r, k, v, w, u, bt=32, interpret=True)
    y128, s128 = wkv6_pallas(r, k, v, w, u, bt=128, interpret=True)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y128), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s32), np.asarray(s128), rtol=1e-6)


def test_wkv6_matches_model_layer():
    """Kernel output == the model's scan implementation (zero init)."""
    from repro.models.rwkv6 import wkv6_scan

    B, S, H, N = 1, 48, 2, 16
    key = jax.random.key(3)
    r = jax.random.normal(key, (B, S, H, N), jnp.float32) * 0.5
    k = jax.random.normal(jax.random.key(4), (B, S, H, N), jnp.float32) * 0.5
    v = jax.random.normal(jax.random.key(5), (B, S, H, N), jnp.float32) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(jax.random.key(6), (B, S, H, N))) * 0.4 + 0.5
    u = jax.random.normal(jax.random.key(7), (H, N), jnp.float32) * 0.1
    state = jnp.zeros((B, H, N, N), jnp.float32)
    y_ref, s_ref_ = wkv6_scan(r, k, v, w, u, state)
    y, s = ops.wkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref_), rtol=2e-5,
                               atol=2e-5)


# ----------------------------------------------------------- segment rowmax
@pytest.mark.parametrize("rows,cols,seg", [(5, 512, 1), (8, 512, 8),
                                           (17, 96, 4), (3, 1024, 64),
                                           (1, 64, 64), (4, 96, 3),
                                           (9, 600, 5), (2, 384, 6)])
def test_segment_rowmax_shapes(rows, cols, seg):
    from repro.kernels.segment_reduce import segment_rowmax_pallas

    vals = jnp.abs(jax.random.normal(jax.random.key(0), (rows, cols),
                                     jnp.float32))
    out = segment_rowmax_pallas(vals, seg, interpret=True)
    _assert_close(out, ref.segment_rowmax(vals, seg), jnp.float32)


@pytest.mark.parametrize("br,bc", [(4, 64), (8, 128), (16, 512)])
def test_segment_rowmax_block_sweep(br, bc):
    from repro.kernels.segment_reduce import segment_rowmax_pallas

    vals = jnp.abs(jax.random.normal(jax.random.key(5), (13, 256),
                                     jnp.float32))
    out = segment_rowmax_pallas(vals, 8, br=br, bc=bc, interpret=True)
    _assert_close(out, ref.segment_rowmax(vals, 8), jnp.float32)


def test_segment_rowmax_ops_wrapper():
    vals = jnp.abs(jax.random.normal(jax.random.key(6), (6, 192),
                                     jnp.float32))
    out = ops.segment_rowmax(vals, 4)
    _assert_close(out, ref.segment_rowmax(vals, 4), jnp.float32)


def test_segment_rowmax_seg_one_is_row_max():
    vals = jnp.abs(jax.random.normal(jax.random.key(7), (9, 300),
                                     jnp.float32))
    out = ops.segment_rowmax(vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(vals).max(axis=1),
                               rtol=1e-6)


# --------------------------------------------------------------- mamba scan
@pytest.mark.parametrize("t,di,n,bt", [(64, 16, 8, 32), (128, 24, 8, 64),
                                       (128, 32, 16, 128)])
def test_mamba_scan_shapes(t, di, n, bt):
    from repro.kernels.mamba_scan import mamba_scan_pallas

    B = 2
    key = jax.random.key(0)
    xs = jax.random.normal(key, (B, t, di), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(1), (B, t, di))) * 0.2
    Bs = jax.random.normal(jax.random.key(2), (B, t, n), jnp.float32) * 0.5
    Cs = jax.random.normal(jax.random.key(3), (B, t, n), jnp.float32) * 0.5
    A = -jnp.exp(jax.random.normal(jax.random.key(4), (di, n)) * 0.3)
    y, s = mamba_scan_pallas(xs, dt, Bs, Cs, A, bt=bt, interpret=True)
    ye, se = ref.mamba_scan(xs, dt, Bs, Cs, A)
    _assert_close(y, ye, jnp.float32)
    _assert_close(s, se, jnp.float32)


def test_mamba_scan_matches_model_mixer():
    """Kernel == the hymba model's mamba recurrence (same discretization)."""
    from repro.configs import get_config
    from repro.models import build
    from repro.models.hymba import d_inner, mamba_mixer

    cfg = get_config("hymba-1.5b").reduced()
    model = build(cfg)
    params = model.init(jax.random.key(0))
    layer0 = jax.tree.map(lambda p: p[0], params["layers"])["mamba"]
    x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model), jnp.float32)
    out_model, state_model, _ = mamba_mixer(layer0, x, cfg)

    # Rebuild the kernel inputs exactly as the mixer does.
    di, n = d_inner(cfg), cfg.ssm_state
    xz = x @ layer0["w_in"]
    xs, z = jnp.split(xz, 2, axis=-1)
    from repro.models.hymba import _causal_conv

    xs, _ = _causal_conv(xs, layer0["conv"])
    xs = jax.nn.silu(xs)
    bc = xs @ layer0["w_bc"]
    B_ssm, C_ssm = jnp.split(bc, 2, axis=-1)
    dt_raw = (xs @ layer0["w_dt"]) @ layer0["w_dt_out"]
    dt = jax.nn.softplus(dt_raw + layer0["dt_bias"])
    A = -jnp.exp(layer0["A_log"])
    y, s = ops.mamba_scan(xs, dt, B_ssm, C_ssm, A)
    y = y + xs * layer0["D"]
    y = y * jax.nn.silu(z)
    out_kernel = y @ layer0["w_out"]
    # kernel multiplies (dt*x)*B, mixer (dt*B)*x — the fp32 reordering
    # amplifies through the 64-step exp-state recurrence (~0.5% worst rel).
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               rtol=1e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(s), np.asarray(state_model),
                               rtol=1e-2, atol=5e-2)
