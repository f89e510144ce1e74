"""Pipeline parallelism over the pod axis (beyond-paper feature).

GPipe-style schedule expressed with shard_map + ppermute over the ``pod``
axis: layers are split into ``pp`` contiguous stages, microbatches stream
through with a lax.scan; the stage handoff is a single ppermute (neighbor
traffic on the DCN -- exactly where the paper's orchestrator wants it,
since aligned ranks sit under one ToR).

This utility pipelines any per-stage function ``stage_fn(stage_idx, x)``;
the trainer wires model stages in when ``pp > 1`` is configured.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def gpipe(stage_fn: Callable, x_mb: jnp.ndarray, *, axis: str,
          n_micro: int) -> jnp.ndarray:
    """Run microbatches through pipeline stages laid on mesh axis ``axis``.

    x_mb: (n_micro, mb, ...) microbatched input, already sharded so that
    stage 0's shard holds the data (others hold zeros/don't care).
    Returns the final-stage outputs in the same microbatch layout.

    Schedule: n_micro + pp - 1 ticks; at each tick every stage processes
    the microbatch it holds and passes the result to the next stage via
    collective-permute (the bubble is (pp-1)/n_micro as usual).
    """
    pp = lax.axis_size(axis)
    stage = lax.axis_index(axis)
    perm = [(i, i + 1) for i in range(pp - 1)]

    ticks = n_micro + pp - 1
    buf_shape = x_mb.shape[1:]

    def tick(carry, t):
        outputs, inflight = carry
        # stage 0 injects microbatch t (if any left)
        inject = jnp.where(t < n_micro, 1, 0)
        idx = jnp.clip(t, 0, n_micro - 1)
        x_in = jnp.where(jnp.logical_and(stage == 0, inject),
                         x_mb[idx], inflight)
        y = stage_fn(stage, x_in)
        # pass to the next stage
        nxt = lax.ppermute(y, axis, perm)
        # last stage retires microbatch t - (pp - 1)
        out_idx = t - (pp - 1)
        valid = jnp.logical_and(stage == pp - 1, out_idx >= 0)
        outputs = jnp.where(
            valid,
            lax.dynamic_update_index_in_dim(
                outputs, y, jnp.clip(out_idx, 0, n_micro - 1), 0),
            outputs)
        return (outputs, nxt), None

    out0 = jnp.zeros((n_micro,) + buf_shape, x_mb.dtype)
    (outputs, _), _ = lax.scan(tick, (out0, jnp.zeros(buf_shape, x_mb.dtype)),
                               jnp.arange(ticks))
    # only the last stage holds retired microbatches; broadcast to all
    return lax.psum(outputs, axis)
