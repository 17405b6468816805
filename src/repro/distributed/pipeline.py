"""Pipeline parallelism: GPipe-style microbatch schedule over a ``stage``
axis with ``shard_map`` + ``lax.ppermute``.

Off by default (the assigned shapes fit DP x TP), provided as the PP
building block for >2-pod scale-out: stages hold disjoint layer ranges;
microbatches stream through with boundary activations handed to the next
stage by ``ppermute``.  The bubble fraction is (S-1)/(M+S-1) for S stages
and M microbatches.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(layer_fn: Callable, n_stages: int, n_microbatches: int,
                     mesh: Mesh, stage_axis: str = "stage"):
    """Returns fn(stage_params, x_microbatches) -> y_microbatches.

    ``stage_params``: pytree with leading stage dim (sharded over
    ``stage_axis``); ``x_microbatches``: (M, mb, ...) inputs.
    ``layer_fn(params_for_stage, x) -> x``.
    """

    def stage_body(params_local, xs_local):
        # params_local: this stage's params (leading dim 1); xs: (M, mb, ...)
        params = jax.tree_util.tree_map(lambda p: p[0], params_local)
        sid = jax.lax.axis_index(stage_axis)
        M = xs_local.shape[0]
        S = n_stages
        n_ticks = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            buf, outs = carry          # buf: (mb, ...) current stage input
            mb_idx = t - sid
            take = jnp.logical_and(mb_idx >= 0, mb_idx < M)
            x_in = jnp.where(
                sid == 0,
                xs_local[jnp.clip(mb_idx, 0, M - 1)],
                buf)
            y = layer_fn(params, x_in)
            y = jnp.where(take[..., None, None] if y.ndim > 2 else take, y,
                          jnp.zeros_like(y))
            # hand off to next stage
            nxt = jax.lax.ppermute(y, stage_axis, perm)
            out_idx = t - (S - 1)
            is_out = jnp.logical_and(sid == S - 1,
                                     jnp.logical_and(out_idx >= 0,
                                                     out_idx < M))
            outs = jax.lax.cond(
                is_out,
                lambda o: o.at[jnp.clip(out_idx, 0, M - 1)].set(y),
                lambda o: o, outs)
            return (nxt, outs), None

        buf0 = jnp.zeros_like(xs_local[0])
        outs0 = jnp.zeros_like(xs_local)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(n_ticks))
        return outs

    return jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(P(stage_axis), P(None)),
        out_specs=P(None),
        check_vma=False)
