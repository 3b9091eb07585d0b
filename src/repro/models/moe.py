"""Mixture-of-Experts FFN: top-k routing, capacity dispatch, EP sharding.

Sort-free capacity-based dispatch (GShard/Switch style, cumsum positions):
avoids the (tokens, experts, capacity) one-hot blowup by scattering through
flat indices — O(N*K*E) routing metadata, O(E*C*D) expert activations.
Routed experts are sharded over the 'model' mesh axis (expert parallelism);
XLA lowers the dispatch/combine scatters into all-to-alls. Expert counts
that do not divide the axis (qwen2-moe: 60) are padded with never-routed
dummy experts (masked at the router).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.params import ParamDef, normal_init
from repro.models.sharding import constrain
from repro.models import layers

CAPACITY_FACTOR = 1.25

# Below this per-group token count the dense dispatch path uses full
# capacity (C = Ng): routing is then exact (no overflow dropping), at the
# cost of a (G, E, Ng, D) buffer — negligible up to this bound. Above it
# the fixed-capacity production behavior applies, so outputs can differ
# across this boundary by design (dropped overflow tokens).
EXACT_DISPATCH_MAX_TOKENS = 512


def moe_schema(cfg: ModelConfig) -> dict:
    e = cfg.padded_experts
    d, f = cfg.d_model, cfg.moe_d_ff
    schema = {
        "router": ParamDef((d, e), ("embed", "experts"), normal_init(0.02)),
        "w_gate": ParamDef((e, d, f), ("experts", "embed", "ffn")),
        "w_up": ParamDef((e, d, f), ("experts", "embed", "ffn")),
        "w_down": ParamDef((e, f, d), ("experts", "ffn", "embed")),
    }
    if cfg.n_shared_experts:
        shared_ff = cfg.shared_d_ff or cfg.moe_d_ff * cfg.n_shared_experts
        schema["shared"] = layers.swiglu_schema(d, shared_ff)
    return schema


def capacity(n_tokens: int, n_experts: int, topk: int) -> int:
    c = int(n_tokens * topk * CAPACITY_FACTOR / n_experts)
    return max(4, (c + 3) // 4 * 4)


def moe_apply(params, x: jax.Array, cfg: ModelConfig):
    """x: (B, S, D) -> (out, aux_loss). Dispatches to the explicit
    shard_map EP path (train/prefill under a mesh with sequence sharding)
    or the dense pjit path (no mesh / decode)."""
    from repro.models import sharding as shd

    mesh = shd._current_mesh()
    if mesh is not None and "model" in getattr(mesh, "axis_names", ()):
        ep = int(mesh.shape["model"])
        B, S, D = x.shape
        batch_axes = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names)
        dp = 1
        for a in batch_axes:
            dp *= int(mesh.shape[a])
        if (
            shd.seq_axis() == "model"
            and cfg.padded_experts % ep == 0
            and B % max(dp, 1) == 0
            and S % ep == 0
        ):
            return _moe_shard_map(params, x, cfg, mesh, batch_axes, ep, dp)
    return _moe_dense(params, x, cfg)


def _moe_shard_map(params, x, cfg: ModelConfig, mesh, batch_axes, ep, dp):
    """Expert parallelism with explicit all_to_all collectives (the
    DeepSpeed/GShard schedule, TPU-native): each device routes its own
    (batch x seq) token shard into per-expert send buckets with a local
    capacity, all_to_all's the buckets to the expert owners along the
    model axis, runs its local experts, and reverses the exchange."""
    import functools

    from jax.sharding import PartitionSpec as P

    E = cfg.padded_experts
    K = cfg.topk
    E_l = E // ep

    def body(x_l, router, wg, wu, wd):
        Bl, Sl, D = x_l.shape
        Nl = Bl * Sl
        dt = x_l.dtype
        xf = x_l.reshape(Nl, D)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        if E != cfg.n_experts:
            logits = jnp.where(jnp.arange(E) >= cfg.n_experts, -1e30, logits)
        probs = jax.nn.softmax(logits, axis=-1)                # (Nl, E)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9
        )
        # ---- aux loss from psum-averaged stats
        all_axes = tuple(batch_axes) + ("model",)
        n_dev = dp * ep
        me = jax.lax.psum(probs.mean(axis=0), all_axes) / n_dev
        counts = jnp.zeros((E,)).at[expert_idx.reshape(-1)].add(1.0)
        ce = jax.lax.psum(counts, all_axes) / (Nl * K * n_dev)
        aux = cfg.n_experts * jnp.sum(me * ce)
        aux = aux + jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * 1e-4

        # ---- local dispatch into per-expert send buckets
        C = capacity(Nl, cfg.n_experts, K)
        e_flat = expert_idx.reshape(-1)                        # (Nl*K,)
        tok_flat = jnp.repeat(jnp.arange(Nl), K)
        order = jnp.argsort(e_flat, stable=True)
        sorted_e = e_flat[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
        rank_sorted = jnp.arange(e_flat.shape[0]) - starts[sorted_e]
        pos_in_e = jnp.zeros_like(e_flat).at[order].set(rank_sorted)
        keep = pos_in_e < C
        w = (gate_vals.reshape(-1) * keep).astype(dt)
        safe_pos = jnp.where(keep, pos_in_e, C - 1)
        send = jnp.zeros((E, C, D), dt)
        send = send.at[e_flat, safe_pos].add(
            jnp.where(keep[:, None], xf[tok_flat], 0)
        )

        # ---- EP all_to_all: (E, C, D) -> (E_l, ep*C, D)
        recv = jax.lax.all_to_all(
            send.reshape(ep, E_l, C, D), "model", split_axis=0,
            concat_axis=0, tiled=False,
        )
        # recv: (ep, E_l, C, D) — senders stacked on axis 0.
        recv = recv.transpose(1, 0, 2, 3).reshape(E_l, ep * C, D)

        # ---- local expert FFN
        g = jnp.einsum("ecd,edf->ecf", recv, wg.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", recv, wu.astype(dt))
        h = jax.nn.silu(g) * u
        y = jnp.einsum("ecf,efd->ecd", h, wd.astype(dt))

        # ---- reverse exchange: (E_l, ep*C, D) -> (E, C, D)
        y = y.reshape(E_l, ep, C, D).transpose(1, 0, 2, 3)
        y_back = jax.lax.all_to_all(
            y, "model", split_axis=0, concat_axis=0, tiled=False,
        )                                                      # (ep,E_l,C,D)
        y_back = y_back.reshape(E, C, D)

        # ---- combine
        gathered = y_back[e_flat, safe_pos] * w[:, None]
        out = jnp.zeros((Nl, D), dt).at[tok_flat].add(gathered)
        return out.reshape(Bl, Sl, D), aux

    x_spec = P(batch_axes if batch_axes else None, "model", None)
    router_spec = P(None, None)
    w_spec = P("model", None, None)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, router_spec, w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    if cfg.n_shared_experts:
        out = out + layers.swiglu(params["shared"], x)
    return out, aux


def _moe_dense(params, x: jax.Array, cfg: ModelConfig):
    """Dense pjit path (no mesh, or decode steps with few tokens).

    Group-local dispatch: tokens are routed within G independent groups
    (G = number of data shards in production, set by the launcher via
    repro.models.sharding.set_moe_groups). The dispatch buffer is
    (G, E, C, D) sharded (data, model, -, -).
    """
    from repro.models.sharding import moe_groups

    B, S, D = x.shape
    E = cfg.padded_experts
    K = cfg.topk
    N = B * S
    G = moe_groups()
    if N % G != 0:
        G = 1
    Ng = N // G
    xg = constrain(x.reshape(G, Ng, D), ("pod", "data"))

    # ---- router (fp32 for numerics)
    logits = jnp.einsum(
        "gnd,de->gne", xg.astype(jnp.float32),
        params["router"].astype(jnp.float32),
    )
    if E != cfg.n_experts:                      # mask padded dummy experts
        pad_mask = jnp.arange(E) >= cfg.n_experts
        logits = jnp.where(pad_mask, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)                     # (G, Ng, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)             # (G, Ng, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )

    # ---- aux losses (load balance + router z-loss), global
    me = probs.reshape(N, E).mean(axis=0)                       # (E,)
    ce = jnp.zeros((E,)).at[expert_idx.reshape(-1)].add(1.0) / (N * K)
    aux = cfg.n_experts * jnp.sum(me * ce)
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * 1e-4
    aux = aux + zloss

    # ---- capacity-based dispatch (argsort ranking per group: no
    # (N*K, E) one-hot — at 1M tokens x 64 experts that tensor alone
    # would blow past HBM)
    C = capacity(Ng, cfg.n_experts, K)
    if Ng <= EXACT_DISPATCH_MAX_TOKENS:
        # Small-token path (decode steps, small-scale tests): full capacity.
        # Fixed-capacity dropping at tiny N would make teacher-forced decode
        # diverge from the forward pass; the (G, E, Ng, D) buffer is cheap
        # at this scale.
        C = Ng
    NgK = Ng * K
    e_flat = expert_idx.reshape(G, NgK)                         # (G, NgK)
    tok_flat = jnp.broadcast_to(
        jnp.repeat(jnp.arange(Ng), K)[None], (G, NgK)
    )
    order = jnp.argsort(e_flat, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(e_flat, order, axis=1)
    starts = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(E), side="left")
    )(sorted_e)                                                 # (G, E)
    rank_sorted = (
        jnp.arange(NgK)[None] - jnp.take_along_axis(starts, sorted_e, axis=1)
    )
    pos_in_e = jnp.zeros_like(e_flat)
    pos_in_e = jax.vmap(lambda p, o, r: p.at[o].set(r))(
        pos_in_e, order, rank_sorted
    )
    keep = pos_in_e < C
    w = (gate_vals.reshape(G, NgK) * keep).astype(x.dtype)

    g_idx = jnp.broadcast_to(jnp.arange(G)[:, None], (G, NgK))
    safe_pos = jnp.where(keep, pos_in_e, C - 1)
    contrib = jnp.where(
        keep[..., None],
        jnp.take_along_axis(xg, tok_flat[..., None], axis=1),
        0,
    )
    contrib = constrain(contrib, ("pod", "data"))
    buf = jnp.zeros((G, E, C, D), x.dtype)
    buf = buf.at[g_idx, e_flat, safe_pos].add(contrib)
    buf = constrain(buf, ("pod", "data"), "model")   # EP all-to-all boundary

    # ---- expert FFN (experts sharded over 'model', groups over 'data')
    dt = x.dtype
    gh = jnp.einsum("gecd,edf->gecf", buf, params["w_gate"].astype(dt))
    uh = jnp.einsum("gecd,edf->gecf", buf, params["w_up"].astype(dt))
    h = jax.nn.silu(gh) * uh
    y = jnp.einsum("gecf,efd->gecd", h, params["w_down"].astype(dt))
    y = constrain(y, ("pod", "data"), "model")

    # ---- combine back to tokens (reverse all-to-all)
    gathered = y[g_idx, e_flat, safe_pos] * w[..., None]
    gathered = constrain(gathered, ("pod", "data"))
    out = jnp.zeros((G, Ng, D), x.dtype)
    out = out.at[g_idx, tok_flat].add(gathered)
    out = constrain(out, ("pod", "data"))
    out = out.reshape(B, S, D)

    if cfg.n_shared_experts:
        out = out + layers.swiglu(params["shared"], x)
    return out, aux
