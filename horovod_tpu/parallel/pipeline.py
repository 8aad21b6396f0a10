"""Pipeline parallelism over a mesh axis — GPipe-style microbatch rotation.

The reference has NO pipeline parallelism and exposes no user P2P (SURVEY §2.4
"PP: Absent. No P2P send/recv is exposed"). Here PP is first-class and
TPU-native: the layer-stacked parameter pytree is sharded over the ``pp`` mesh
axis on its leading (layer) dimension, so each chip holds a contiguous stage
of layers; activations circulate stage-to-stage with ``lax.ppermute`` (one ICI
neighbour hop), and microbatches are rotated through so all stages compute
concurrently after warm-up (bubble = (pp-1)/(M+pp-1)).

This is plain SPMD: every chip runs the same scanned program; validity masking
(which microbatch a stage holds at step t) is static arithmetic on
axis_index, so XLA sees static shapes and a single fused loop.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(
    stage_fn: Callable[[jax.Array], jax.Array],
    x_microbatches: jax.Array,
    pp_axis: str,
) -> jax.Array:
    """Run a PP-sharded stage function over microbatches.

    Args:
      stage_fn: applies THIS chip's stage (its local layer chunk) to one
        microbatch activation [mb, ...] -> [mb, ...].
      x_microbatches: [M, mb, ...] — all microbatches' stage-0 inputs,
        replicated across ``pp`` (embedding is cheap to compute everywhere;
        only stage 0's copy enters the pipeline).
      pp_axis: mesh axis name the layer stack is sharded over.

    Returns [M, mb, ...] final-stage outputs, replicated across ``pp`` (last
    stage's results are broadcast via a masked psum).

    Schedule: at step t, stage s processes microbatch (t - s); stage 0 feeds
    fresh microbatches, stage pp-1 collects. T = M + pp - 1 steps.
    """
    pp = lax.axis_size(pp_axis)
    s_idx = lax.axis_index(pp_axis)
    n_micro = x_microbatches.shape[0]
    total_steps = n_micro + pp - 1
    # send stage s -> s+1; stage 0 receives nothing real (zeros are fine,
    # masked out by the fresh-input select)
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def step(carry, t):
        state, outputs = carry
        fresh = lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, n_micro - 1), axis=0,
            keepdims=False)
        inp = jnp.where(s_idx == 0, fresh, state)
        out = stage_fn(inp)
        m = t - s_idx
        valid_out = (s_idx == pp - 1) & (m >= 0) & (m < n_micro)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(valid_out, out,
                      lax.dynamic_index_in_dim(
                          outputs, jnp.clip(m, 0, n_micro - 1), axis=0,
                          keepdims=False)),
            jnp.clip(m, 0, n_micro - 1), axis=0)
        state = lax.ppermute(out, pp_axis, perm)
        return (state, outputs), None

    state0 = jnp.zeros_like(x_microbatches[0])
    outputs0 = jnp.zeros_like(x_microbatches)
    (_, outputs), _ = lax.scan(
        step, (state0, outputs0), jnp.arange(total_steps))
    # Only the last stage holds real outputs; everyone else holds zeros.
    # Masked psum broadcasts them across the pp axis.
    outputs = jnp.where(s_idx == pp - 1, outputs, jnp.zeros_like(outputs))
    return lax.psum(outputs, pp_axis)


def stage_layer_slice(n_layers: int, pp: int) -> int:
    """Layers per stage; n_layers must divide evenly across stages."""
    if n_layers % pp != 0:
        raise ValueError(f"{n_layers} layers not divisible by pp={pp}")
    return n_layers // pp
