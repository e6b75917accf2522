"""Pipeline parallelism over the ``pod`` mesh axis (``--pod-mode=pp``).

GPipe-style schedule expressed in jax-native constructs: each pod holds a
contiguous stage of the layer stack; microbatch activations travel between
stages with ``jax.lax.ppermute`` inside shard_map. With S stages and M
microbatches the bubble fraction is (S-1)/(M+S-1) — at S=2 pods, M=8
microbatches it is ~12%, traded against NOT replicating the model across
pods (halves per-pod parameter + optimizer memory vs pod-DP).

This module is deliberately model-agnostic: ``stage_fn(stage_params, x)``
is any per-stage forward. The LM zoo's scanned block stack slots in directly
(stage_params = the [n_rep/S, ...] slice of the block stack).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def gpipe_forward(stage_fn: Callable, stage_params, x_microbatches,
                  mesh, axis: str = "pod"):
    """Run M microbatches through S pipeline stages.

    stage_params : pytree with leading dim S, sharded over ``axis``
                   (each pod holds only its own stage's slice).
    x_microbatches : [M, mb, ...] input microbatches (replicated over axis).
    Returns [M, mb, ...] outputs (valid on the LAST stage; replicated out
    by a final ppermute broadcast).
    """
    S = mesh.shape[axis]
    M = x_microbatches.shape[0]
    perm_fwd = [(i, (i + 1) % S) for i in range(S)]

    def local(stage_p, xs):
        # stage_p: this pod's stage slice ([1, ...] leading dim from sharding)
        stage_p = jax.tree.map(lambda a: a[0], stage_p)
        stage_id = jax.lax.axis_index(axis)
        n_ticks = M + S - 1

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 injects microbatch t (if any left); others use inflight
            mb_idx = jnp.clip(t, 0, M - 1)
            injected = xs[mb_idx]
            x_in = jnp.where(stage_id == 0, injected, inflight)
            y = stage_fn(stage_p, x_in)
            # forward the activation to the next stage
            passed = jax.lax.ppermute(y, axis, perm_fwd)
            # last stage records its result for microbatch (t - (S-1))
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            take = jnp.logical_and(stage_id == S - 1, t >= S - 1)
            outputs = jnp.where(
                take,
                jax.lax.dynamic_update_index_in_dim(outputs, y, out_idx, 0),
                outputs,
            )
            return (passed, outputs), None

        init = (jnp.zeros_like(xs[0]), jnp.zeros_like(xs))
        (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))
        return outputs[None]  # [1, M, ...] per stage; stacked over the axis

    P = jax.sharding.PartitionSpec
    stage_spec = jax.tree.map(lambda _: P(axis), stage_params)
    stacked = jax.shard_map(
        local, mesh=mesh,
        in_specs=(stage_spec, P()),
        out_specs=P(axis),
        check_vma=False,
    )(stage_params, x_microbatches)
    return stacked[-1]  # the last stage holds the real outputs


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
