"""Core NN layers: norms, rotary embeddings, attention (naive / chunked /
decode), MLPs. Pure functions over schema-built param dicts.

Attention memory discipline: seq >= CHUNK_THRESHOLD routes through a
two-level online-softmax (flash-style) jnp implementation so the 32k
prefill never materializes an S^2 score tensor. The Pallas TPU kernel in
repro.kernels.flash_attention mirrors this math; `use_pallas=True` swaps
it in on TPU backends.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.params import ParamDef, normal_init, ones_init, scaled_init, zeros_init

# Above this sequence length attention always takes the online-softmax
# chunked path: a naive (B,H,S,S) fp32 score tensor at S=4096 with
# unsharded heads (FSDP archs) is 28 GiB per device — never materialize it.
CHUNK_THRESHOLD = 2048
Q_CHUNK = 1024
KV_CHUNK = 1024
NEG_INF = -1e30


# ------------------------------------------------------------------- norms
def rmsnorm_schema(dim: int) -> dict:
    return {"scale": ParamDef((dim,), ("embed",), ones_init())}


def rmsnorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


# ------------------------------------------------------------------ rotary
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                       # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------- attention
def _mask_bias(q_pos, k_pos, window: int):
    """Causal (+ sliding window) additive bias; shapes broadcast."""
    causal = q_pos[..., :, None] >= k_pos[..., None, :]
    ok = causal
    if window > 0:
        ok = ok & (q_pos[..., :, None] - k_pos[..., None, :] < window)
    return jnp.where(ok, 0.0, NEG_INF)


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def naive_attention(q, k, v, *, window: int = 0, scale: float | None = None):
    """q: (B,S,H,hd), k/v: (B,S,Kv,hd) -> (B,S,H,hd). For short seqs."""
    B, S, H, hd = q.shape
    Kv = k.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    k = _repeat_kv(k, H // Kv)
    v = _repeat_kv(v, H // Kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    pos = jnp.arange(S)
    scores = scores + _mask_bias(pos, pos, window)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def chunked_attention(q, k, v, *, window: int = 0, scale: float | None = None,
                      q_chunk: int = Q_CHUNK, kv_chunk: int = KV_CHUNK,
                      q_offset=0):
    """Two-level online-softmax attention (flash-style, pure jnp).

    Never materializes more than (B, H, q_chunk, kv_chunk) of scores.
    ``q_offset``: global position of q[:, 0] (sequence-parallel shards).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]                 # may exceed Sq (SP: local q, full k/v)
    Kv = k.shape[2]
    hd_v = v.shape[-1]              # may differ from hd (MLA: 192 qk / 128 v)
    scale = scale if scale is not None else hd ** -0.5
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0, (Sq, Sk, q_chunk, kv_chunk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    groups = H // Kv

    qr = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 3, 2, 4)  # (nq,B,H,qc,hd)
    kr = k.reshape(B, nk, kv_chunk, Kv, hd).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(B, nk, kv_chunk, Kv, hd_v).transpose(1, 0, 3, 2, 4)

    def q_step(qi, q_blk):
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, inputs):
            ki, k_blk, v_blk = inputs
            acc, m, denom = carry
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            k_rep = jnp.repeat(k_blk, groups, axis=1)   # (B,H,kc,hd)
            v_rep = jnp.repeat(v_blk, groups, axis=1)
            s = (
                jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_rep).astype(jnp.float32)
                * scale
            )
            s = s + _mask_bias(q_pos, k_pos, window)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            denom = denom * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(q.dtype), v_rep
            ).astype(jnp.float32)
            return (acc, m_new, denom), None

        acc0 = jnp.zeros((B, H, q_chunk, hd_v), jnp.float32)
        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        d0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        (acc, m, denom), _ = jax.lax.scan(
            kv_step, (acc0, m0, d0), (jnp.arange(nk), kr, vr)
        )
        out = acc / jnp.maximum(denom[..., None], 1e-30)
        return out.astype(q.dtype)                       # (B,H,qc,hd)

    outs = jax.lax.map(lambda args: q_step(*args), (jnp.arange(nq), qr))
    # (nq,B,H,qc,hd_v) -> (B, Sq, H, hd_v)
    return outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, hd_v)


def sp_attention(q, k, v, *, window: int = 0, scale: float | None = None):
    """Sequence-parallel attention: explicit shard_map over the mesh.

    q/k/v arrive seq-sharded over 'model'. Each device all-gathers K/V
    (bf16 — 2 gathers per layer) and runs the online-softmax kernel on its
    LOCAL q shard with the correct global position offset. Without this,
    the SPMD partitioner reshards the (B, H, qc, kc) fp32 score blocks of
    the chunk loop — tens of GiB of gathers per layer.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.models import sharding as shd

    mesh = shd._current_mesh()
    ep = int(mesh.shape["model"])
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    S_l = q.shape[1] // ep

    def body(q_l, k_l, v_l):
        k_f = jax.lax.all_gather(k_l, "model", axis=1, tiled=True)
        v_f = jax.lax.all_gather(v_l, "model", axis=1, tiled=True)
        q_offset = jax.lax.axis_index("model") * S_l
        return chunked_attention(
            q_l, k_f, v_f, window=window, scale=scale, q_offset=q_offset,
            q_chunk=min(Q_CHUNK, S_l),
        )

    spec = P(batch_axes if batch_axes else None, "model", None, None)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _sp_attention_applicable(q, k) -> bool:
    from repro.models import sharding as shd

    try:
        from repro.launch.knobs import active

        if not active().sp_attention:
            return False
    except Exception:
        pass
    if shd.seq_axis() != "model":
        return False
    mesh = shd._current_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return False
    ep = int(mesh.shape["model"])
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in batch_axes:
        dp *= int(mesh.shape[a])
    return (
        q.shape[1] % ep == 0
        and q.shape[0] % max(dp, 1) == 0
        and (q.shape[1] // ep) >= 128
    )


def attention(q, k, v, *, window: int = 0, scale: float | None = None,
              use_pallas: bool = False):
    if use_pallas:
        from repro.kernels import ops as kops

        return kops.flash_attention(q, k, v, window=window, scale=scale)
    if _sp_attention_applicable(q, k):
        return sp_attention(q, k, v, window=window, scale=scale)
    if q.shape[1] > CHUNK_THRESHOLD:
        return chunked_attention(q, k, v, window=window, scale=scale)
    return naive_attention(q, k, v, window=window, scale=scale)


def sp_decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                        scale: float | None = None):
    """Flash-decoding over a sequence-sharded KV cache (shard_map).

    When kv-heads don't divide the model axis the cache shards on its
    SEQUENCE dim; gathering K/V per layer costs GiBs per step. Instead,
    each device computes attention against its local cache slice and the
    shards merge with the online-softmax combine (pmax/psum of
    exp-weighted partials) — collective traffic is O(B*H*hd), not O(C).
    """
    from jax.sharding import PartitionSpec as P

    from repro.models import sharding as shd

    mesh = shd._current_mesh()
    ep = int(mesh.shape["model"])
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B, C, Kv, hd = k_cache.shape
    H = q.shape[2]
    sc = scale if scale is not None else hd ** -0.5
    C_l = C // ep

    def body(q_l, k_l, v_l):
        shard = jax.lax.axis_index("model")
        k = _repeat_kv(k_l, H // Kv)
        v = _repeat_kv(v_l, H // Kv)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_l, k).astype(jnp.float32) * sc
        slot = shard * C_l + jnp.arange(C_l)
        if window > 0:
            valid = slot[None, None, None, :] <= jnp.minimum(pos, C - 1)
            valid = jnp.where(pos >= C, jnp.ones_like(valid), valid)
        else:
            valid = slot[None, None, None, :] <= pos
        s = jnp.where(valid, s, NEG_INF)
        m_l = s.max(axis=-1)                              # (B,H,1)
        p = jnp.exp(s - m_l[..., None])
        d_l = p.sum(axis=-1)
        acc_l = jnp.einsum("bhqk,bkhd->bhqd", p.astype(q_l.dtype), v
                           ).astype(jnp.float32)
        # online-softmax merge across shards
        m = jax.lax.pmax(m_l, "model")
        w = jnp.exp(m_l - m)
        d = jax.lax.psum(d_l * w, "model")
        acc = jax.lax.psum(acc_l * w[..., None], "model")
        out = acc / jnp.maximum(d[..., None], 1e-30)
        # (B,H,1,hd) -> (B,1,H,hd)
        return out.transpose(0, 2, 1, 3).astype(q_l.dtype)

    bspec = batch_axes if batch_axes else None
    q_spec = P(bspec, None, None, None)
    kv_spec = P(bspec, "model", None, None)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k_cache, v_cache)


def _sp_decode_applicable(q, k_cache) -> bool:
    from repro.models import sharding as shd

    try:
        from repro.launch.knobs import active

        if not active().sp_attention:
            return False
    except Exception:
        pass
    mesh = shd._current_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return False
    ep = int(mesh.shape["model"])
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in batch_axes:
        dp *= int(mesh.shape[a])
    B, C, Kv, _ = k_cache.shape
    # policy shards the cache seq dim only when kv heads don't divide
    return Kv % ep != 0 and C % ep == 0 and B % max(dp, 1) == 0


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     scale: float | None = None):
    """One-token attention against a cache.

    q: (B, 1, H, hd); k/v_cache: (B, C, Kv, hd); pos: scalar current index
    (number of tokens already in cache, 0-based insert position).
    For sliding windows the cache is a ring buffer of capacity C=window and
    slot validity is derived from pos.
    """
    if _sp_decode_applicable(q, k_cache):
        return sp_decode_attention(q, k_cache, v_cache, pos, window=window,
                                   scale=scale)
    B, C, Kv, hd = k_cache.shape
    H = q.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    k = _repeat_kv(k_cache, H // Kv)
    v = _repeat_kv(v_cache, H // Kv)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    slot = jnp.arange(C)
    if window > 0:
        # Ring buffer: slots hold tokens (pos - C, pos]; valid if < pos+1.
        valid = slot[None, None, None, :] <= jnp.minimum(pos, C - 1)
        # After wrap, every slot is valid.
        valid = jnp.where(pos >= C, jnp.ones_like(valid), valid)
    else:
        valid = slot[None, None, None, :] <= pos
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# -------------------------------------------------------------------- MLPs
def swiglu_schema(d_model: int, d_ff: int) -> dict:
    return {
        "w_gate": ParamDef((d_model, d_ff), ("embed", "ffn")),
        "w_up": ParamDef((d_model, d_ff), ("embed", "ffn")),
        "w_down": ParamDef((d_ff, d_model), ("ffn", "embed")),
    }


def swiglu(params, x):
    dtype = x.dtype
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(dtype))
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"].astype(dtype))


# --------------------------------------------------------------- embedding
def embedding_schema(vocab: int, d_model: int) -> dict:
    return {"table": ParamDef((vocab, d_model), ("vocab", "embed"),
                              normal_init(0.02))}


def embed(params, ids, dtype):
    return params["table"].astype(dtype)[ids]


def unembed(params, x, table=None):
    t = (table if table is not None else params["table"]).astype(x.dtype)
    return jnp.einsum("bsd,vd->bsv", x, t)
