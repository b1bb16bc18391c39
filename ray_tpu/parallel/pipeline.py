"""Pipeline parallelism: shard_map microbatch pipeline over the `pp` axis.

The reference has no native PP (SURVEY.md §2.4 — DeepSpeed/Alpa only).
TPU-native design: the layer stack is sharded over the `pp` mesh axis
(stage i holds layers [i·L/p, (i+1)·L/p)); microbatches stream through
stages with `lax.ppermute` moving activations to the next stage each step.
This is the GPipe schedule expressed as a compiled collective program —
XLA overlaps the ppermute with the next microbatch's compute on ICI.

Use inside shard_map: params' leading axis is the stage axis (size p per
device after sharding), inputs are microbatched on the leading axis.
"""

from __future__ import annotations

from typing import Callable

import jax

from ray_tpu.util.collective.ops import axis_size as _axis_size
import jax.numpy as jnp
from jax import lax


def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches,
                   axis: str = "pp"):
    """Run a GPipe-style pipeline inside shard_map.

    stage_fn(params, x) -> y : one stage's computation (same shape in/out).
    stage_params: this device's stage parameters (layers of my stage).
    x_microbatches: (num_micro, mb, ...) — every device receives the full
      microbatched input; stage 0 feeds real inputs, later stages consume
      what arrives over the ring. Output: (num_micro, mb, ...) valid on the
      LAST stage (others hold garbage; caller selects).

    Total steps = num_micro + num_stages - 1 (fill + drain).
    """
    n_stages = _axis_size(axis)
    stage = lax.axis_index(axis)
    num_micro = x_microbatches.shape[0]
    mb_shape = x_microbatches.shape[1:]
    total_steps = num_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(step, carry):
        state, outputs = carry
        # Stage 0 ingests microbatch `step` (if within range); other stages
        # use the activation that just arrived from the previous stage.
        mb_idx = jnp.clip(step, 0, num_micro - 1)
        fresh = lax.dynamic_index_in_dim(x_microbatches, mb_idx, axis=0,
                                         keepdims=False)
        x_in = jnp.where(stage == 0, fresh, state)
        y = stage_fn(stage_params, x_in)
        # Last stage writes its result for microbatch (step - n_stages + 1).
        out_idx = jnp.clip(step - (n_stages - 1), 0, num_micro - 1)
        write = jnp.logical_and(stage == n_stages - 1,
                                step >= n_stages - 1)
        cur = lax.dynamic_index_in_dim(outputs, out_idx, axis=0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, y, cur), out_idx, axis=0)
        # Rotate activations to the next stage.
        state = lax.ppermute(y, axis, perm)
        return state, outputs

    # Carries vary over the pipeline axis (ppermute) AND any axes the input
    # varies over (e.g. dp-sharded batch): adding 0·x unions the two sets.
    def _vary(val):
        return lax.pcast(val, (axis,), to="varying")

    zero_like_x = jnp.zeros(mb_shape, x_microbatches.dtype) + \
        x_microbatches[0] * 0
    state0 = _vary(zero_like_x)
    outputs0 = _vary(jnp.zeros_like(x_microbatches) + x_microbatches * 0)
    _, outputs = lax.fori_loop(0, total_steps, body, (state0, outputs0))
    # Results are only valid on the last stage; broadcast so every stage
    # returns them (psum of a one-hot-masked value = ICI broadcast).
    outputs = lax.psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis)
    return outputs


def split_microbatches(x, num_micro: int):
    """(B, ...) → (num_micro, B/num_micro, ...)."""
    B = x.shape[0]
    if B % num_micro:
        raise ValueError(f"batch {B} not divisible by {num_micro} microbatches")
    return x.reshape(num_micro, B // num_micro, *x.shape[1:])


def merge_microbatches(y):
    return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])


def stage_slice_params(params, n_stages: int, stage_axis: int = 0):
    """Utility for tests/single-host: split a stacked-layer param tree into
    per-stage chunks along the layer axis."""
    def split(leaf):
        L = leaf.shape[stage_axis]
        if L % n_stages:
            raise ValueError(f"layer count {L} not divisible by {n_stages} stages")
        return leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])

    return jax.tree_util.tree_map(split, params)
