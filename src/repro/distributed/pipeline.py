"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis
(DESIGN.md §5) via shard_map + collective_permute.

At 1000+ nodes the third parallelism axis after DP and TP is the layer
pipeline.  This module implements the schedule explicitly (pjit cannot
express it): the layer stack is split into ``pipe`` stages; microbatches
stream through, each stage running its local layers and permuting
activations to the next stage.  The bubble fraction is the standard
(P-1)/(M+P-1).

The stage function is user-supplied (params_stage, x) -> x, so any of
the repro models' layer stacks can ride the pipeline; the unit test
drives a toy MLP stack and checks exact equivalence with the sequential
stack.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_apply(
    stage_fn: Callable,
    mesh,
    n_microbatches: int,
    axis: str = "pipe",
):
    """Build pipelined_apply(stage_params, x) -> y.

    stage_params: pytree with leading dim = n_stages (sharded over
    ``axis``); x: (batch, ...) global batch, split into n_microbatches.
    stage i processes microbatch m at step t = i + m; activations move
    stage->stage+1 with collective_permute.
    """
    n_stages = mesh.shape[axis]

    def local(params_stage, x):
        # params_stage: this stage's params (leading dim 1 from sharding)
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        stage = jax.lax.axis_index(axis)
        mbs = x.reshape((n_microbatches, -1) + x.shape[1:])
        n_steps = n_microbatches + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def step(carry, t):
            buf, out = carry  # buf: the activation entering this stage
            # stage 0 feeds itself from the microbatch queue
            idx = jnp.clip(t, 0, n_microbatches - 1)
            inject = mbs[idx]
            x_in = jnp.where(stage == 0, inject, buf)
            active = (t - stage >= 0) & (t - stage < n_microbatches)
            y = stage_fn(params_stage, x_in)
            y = jnp.where(active, y, buf)
            # last stage collects its finished microbatch
            out_idx = jnp.clip(t - stage, 0, n_microbatches - 1)
            collect = active & (stage == n_stages - 1)
            out = jax.lax.cond(
                collect,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0
                ),
                lambda o: o,
                out,
            )
            # shift activations to the next stage
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, out), None

        buf0 = jnp.zeros_like(mbs[0])
        out0 = jnp.zeros_like(mbs)
        (_, out), _ = jax.lax.scan(
            step, (buf0, out0), jnp.arange(n_steps)
        )
        # only the last stage holds real outputs; broadcast via psum of
        # the masked buffer (ppermute sources must be unique)
        out = jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out))
        out = jax.lax.psum(out, axis)
        return out.reshape((-1,) + x.shape[1:])

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
