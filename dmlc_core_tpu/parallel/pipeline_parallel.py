"""Pipeline parallelism: GPipe-style microbatch scheduling over a mesh
axis, SPMD-formulated.

Unlike the MPMD pipeline runtimes the GPU ecosystem hand-rolls, a TPU
pipeline is just another SPMD program (the scaling-book formulation):
every device runs the SAME step function; the stage's weights are the
device's shard of a leading-stage-axis parameter stack, and activations
move stage->stage with one ``ppermute`` per tick. A schedule of
``M + P - 1`` ticks drains M microbatches through P stages; autodiff
through the ticks yields the backward pipeline for free (the transpose
of ppermute is the reverse ppermute).

``pipeline_apply`` is the generic schedule; it runs inside ``shard_map``
over the "pipe" axis and composes with a "data" axis outside it.
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
from jax import lax

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn: Callable, stage_params, microbatches,
                   axis_name: str = "pipe") -> jnp.ndarray:
    """Drain microbatches through the stage pipeline; returns their outputs.

    Args (inside a shard_map over ``axis_name``):
      stage_fn: (params_slice, x) -> y, the per-stage computation; input
        and output activations must share one shape (the classic GPipe
        homogeneous-stage contract).
      stage_params: THIS stage's parameter pytree (the shard_map slice of
        a leading-axis stack sharded over ``axis_name``, squeezed).
      microbatches: [M, ...] activations fed to stage 0, replicated
        across the pipe axis.

    Returns [M, ...] outputs of the LAST stage, identical on every pipe
    rank (a psum broadcasts them, so downstream loss code is
    position-independent).
    """
    num_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    zero = jnp.zeros_like(microbatches[0])
    fwd = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    # one lax.scan tick per schedule slot: compile size stays constant in
    # M and stage count (stage_fn traces once), unlike an unrolled loop
    pad = jnp.zeros((num_stages - 1,) + microbatches.shape[1:],
                    microbatches.dtype)
    injections = jnp.concatenate([microbatches, pad], axis=0)

    def tick(state, inject):
        # stage 0 injects the next microbatch while it lasts; later
        # stages take the activation handed to them on the previous tick
        x = jnp.where(stage == 0, inject, state)
        y = stage_fn(stage_params, x)
        out = jnp.where(stage == num_stages - 1, y, zero)
        # hand activations to the next stage (the wrap-around edge only
        # ever carries finished outputs back to stage 0's ignored input)
        return lax.ppermute(y, axis_name, fwd), out

    # the carry must enter the scan with the same device-varying type the
    # ppermute output carries (shard_map's varying-type discipline)
    from dmlc_core_tpu.parallel.varying import mark_varying
    state0 = mark_varying(zero, (axis_name,))
    _, ys = lax.scan(tick, state0, injections)
    # the last stage finishes microbatch m at tick m + (P-1)
    outs = ys[num_stages - 1:]
    # broadcast the last stage's outputs to every pipe rank
    return lax.psum(outs, axis_name)
