"""Pipeline parallelism across pods (GPipe-style, shard_map + ppermute).

Multi-pod meshes pay DCI prices for cross-pod collectives; pipelining
sends only ACTIVATIONS across the pod boundary instead of gradient
all-reduces. The layer stack is split into one contiguous stage per pod;
microbatches stream through the classic skewed schedule:

    t:        0    1    2    3   ...
    stage 0:  m0   m1   m2   m3
    stage 1:       m0   m1   m2

Implemented as a shard_map over the 'pod' axis whose body runs the local
stage and collective_permutes activations to the next stage. Bubble
fraction = (S-1)/(M+S-1). jax.grad differentiates straight through (the
transpose of ppermute is the reverse permute), giving a correct (GPipe,
all-microbatch-stash) backward.

This module is self-contained and validated against the unpipelined
reference on 8 fake devices (tests/test_pipeline.py); it is the
distribution feature the 'pod' axis exists for at 1000+ nodes.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def split_stages(stacked_params: Any, n_stages: int) -> Any:
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major."""

    def reshape(p):
        L = p.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])

    return jax.tree.map(reshape, stacked_params)


def pipelined_apply(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    mesh,
    *,
    pod_axis: str = "pod",
    n_microbatches: int,
):
    """Build fn(stage_params, x) -> y running the layer stack pipelined.

    ``layer_fn(layer_params, x) -> x`` applies ONE layer. ``stage_params``
    is the (S, L/S, ...) tree from split_stages, sharded over the pod axis
    on dim 0; ``x`` is (M*Bm, ...) microbatch-major, replicated across the
    pod axis (each stage uses only its schedule slice).
    """
    n_stages = int(mesh.shape[pod_axis])

    def stage_apply(local_stack, x):
        def body(h, layer_params):
            return layer_fn(layer_params, h), None

        out, _ = jax.lax.scan(body, x, local_stack)
        return out

    def body(stage_stack, x_all):
        # stage_stack: (1, L/S, ...) local slice; x_all: (M, Bm, ...).
        local = jax.tree.map(lambda p: p[0], stage_stack)
        stage = jax.lax.axis_index(pod_axis)
        M = x_all.shape[0]
        T = M + n_stages - 1
        carry_in = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros_like(x_all)

        def step(t, state):
            carry, outputs = state
            # stage 0 ingests microbatch t (when valid); others take the
            # activation handed over at the previous tick.
            mb_idx = jnp.clip(t, 0, M - 1)
            feed = jnp.where(
                stage == 0,
                jax.lax.dynamic_index_in_dim(x_all, mb_idx, 0, keepdims=False),
                carry,
            )
            out = stage_apply(local, feed)
            # hand to the next stage (ring; the wraparound write is masked)
            nxt = jax.lax.ppermute(
                out, pod_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            # last stage emits microbatch (t - (S-1)) at tick t
            emit_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            valid = (stage == n_stages - 1) & (t >= n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, emit_idx, 0,
                                               keepdims=False)
            newval = jnp.where(valid, out, cur)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, newval, emit_idx, 0
            )
            return (nxt, outputs)

        _, outputs = jax.lax.fori_loop(0, T, step, (carry_in, outputs))
        # Make the result identical on every pod (the last stage owns it).
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0), pod_axis
        )
        return outputs

    # P(pod_axis) acts as a pytree prefix: dim 0 (the stage dim) of every
    # parameter leaf shards over the pod axis.
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(pod_axis), P()),
        out_specs=P(),
        check_vma=False,
    )

    def apply(stage_params, x_microbatched):
        return fn(stage_params, x_microbatched)

    return apply


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
