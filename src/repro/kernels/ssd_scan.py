"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

One grid step processes one (batch, head, chunk) cell:  the intra-chunk
quadratic term (decay-masked scores) runs on the MXU while the inter-chunk
state (N, P) lives in VMEM scratch and carries across the chunk axis (grid
is sequential over its last dimension on TPU).  This is the zamba2 /
long-context hot spot: state size is constant in sequence length.

Inputs are laid out (B, H, S, ·) so the chunk axis tiles the
second-to-last dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(la_ref, k_ref, v_ref, q_ref, o_ref, state_scr, *, Q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    la = la_ref[0, 0, 0].astype(jnp.float32)        # (1, Q) log-decays
    k = k_ref[0, 0].astype(jnp.float32)             # (Q, N)
    v = v_ref[0, 0].astype(jnp.float32)             # (Q, P)
    q = q_ref[0, 0].astype(jnp.float32)             # (Q, N)

    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # inclusive cumsum as triangular matmuls (Mosaic has no cumsum), as a
    # row and as a column: cum[j] = sum_{k<=j} la[k]
    upper = (rows <= cols).astype(jnp.float32)      # [k, j] = k <= j
    cum = jax.lax.dot_general(la, upper, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # (1, Q)
    cum_t = jax.lax.dot_general(upper, la, (((0,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)  # (Q, 1)
    # intra-chunk decay mask: exp(cum_i - cum_j) for i >= j else 0
    seg = cum_t - cum                               # (Q, Q): [i, j]
    mask = jnp.where(rows >= cols, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * mask
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)
    # inter-chunk: y += (q * exp(cum)) @ S_prev
    y += jax.lax.dot_general(q * jnp.exp(cum_t), state_scr[...],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # state update: S = exp(cum[-1]) * S + (k * exp(cum[-1] - cum))^T @ v
    total = cum[0, Q - 1]
    dec_out = jnp.exp(total - cum_t)                # (Q, 1)
    state_scr[...] = jnp.exp(total) * state_scr[...] + jax.lax.dot_general(
        k * dec_out, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(a: jax.Array, k: jax.Array, v: jax.Array, q: jax.Array, *,
             chunk: int = 256, interpret: bool = False) -> jax.Array:
    """SSD scan  S_t = a_t S_{t-1} + k_t v_t^T ;  y_t = S_t^T q_t.

    a: (B, H, S) decays in (0,1]; k, q: (B, H, S, N); v: (B, H, S, P).
    S must be a multiple of ``chunk`` (ops.py pads).  Returns (B, H, S, P).
    """
    B, H, S = a.shape
    N = k.shape[-1]
    P = v.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    la = jnp.log(jnp.maximum(a.astype(jnp.float32), 1e-37))
    la = la.reshape(B, H, nc, 1, chunk)
    kernel = functools.partial(_ssd_kernel, Q=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), v.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(la, k, v, q)
