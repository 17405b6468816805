"""Shared model primitives: norms, activations, rotary embeddings, init."""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

DEFAULT_COMPUTE_DTYPE = jnp.bfloat16
DEFAULT_PARAM_DTYPE = jnp.bfloat16

# ---------------------------------------------------------------------------
# Mesh hint: the launch layer registers the active mesh so model code can
# constrain activation shardings (batch over DP axes, hidden over "model")
# without importing the launch layer.  ``None`` (tests, single device) makes
# constraints no-ops.
_MESH_HINT = None


def set_mesh_hint(mesh) -> None:
    global _MESH_HINT
    _MESH_HINT = mesh


def get_mesh_hint():
    return _MESH_HINT


def shard_hint(x: "jax.Array", *axes) -> "jax.Array":
    """Apply a sharding constraint if a mesh hint is active.

    ``axes``: per-dim axis roles; "dp" expands to ("pod", "data")."""
    mesh = _MESH_HINT
    if mesh is None:
        return x
    from ..distributed.sharding import dp_axes, fit  # local: avoid cycle
    resolved = tuple(dp_axes(mesh) if a == "dp" else a for a in axes)
    spec = fit(mesh, x.shape, *resolved)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


# ---------------------------------------------------------------- norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------- activations
def squared_relu(x: jax.Array) -> jax.Array:
    r = jax.nn.relu(x)
    return r * r


def gelu_exact(x: jax.Array) -> jax.Array:
    """GELU by the error function (``"gelu"`` is the tanh approximation)."""
    return jax.nn.gelu(x, approximate=False)


ACTIVATIONS: dict = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "gelu_exact": gelu_exact,
    "relu": jax.nn.relu,
    "squared_relu": squared_relu,
}


# ---------------------------------------------------------------- rotary
def rope_frequencies(head_dim: int, max_pos: int, theta: float = 10000.0,
                     rotary_dim: Optional[int] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables of shape (max_pos, rotary_dim // 2), float32."""
    rd = rotary_dim or head_dim
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    pos = jnp.arange(max_pos, dtype=jnp.float32)
    ang = jnp.outer(pos, inv)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: Optional[jax.Array] = None,
               rotary_dim: Optional[int] = None) -> jax.Array:
    """Rotate pairs (interleaved-half convention).  ``x``: (..., S, H, D);
    ``positions``: (..., S) token positions (defaults to arange)."""
    D = x.shape[-1]
    rd = rotary_dim or D
    if positions is None:
        S = x.shape[-3]
        positions = jnp.arange(S)
        c = cos[positions][..., None, :]       # (S, 1, rd/2)
        s = sin[positions][..., None, :]
    else:
        c = cos[positions][..., None, :]       # (..., S, 1, rd/2)
        s = sin[positions][..., None, :]
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1.astype(x.dtype), out2.astype(x.dtype), xp],
                           axis=-1)


# ----------------------------------------------------------- embedding
def embed_lookup(table: jax.Array, tokens: jax.Array,
                 tied: bool = False) -> jax.Array:
    """Embedding gather with a sharding-disciplined backward pass.

    XLA's SPMD partitioner handles neither the vocab-sharded gather nor its
    scatter-add transpose efficiently at 256k-vocab/18k-d scale (it
    replicates full-batch fp32 hidden tensors).  Both directions are
    therefore written with ``shard_map``:

    * untied: table d-sharded over "model" — gather and scatter fully local
      per d-slice, grads psum'd over the DP axes.
    * tied: table vocab-sharded over "model" (the head needs vocab-parallel
      logits) — masked local gather + psum over "model".
    """
    import numpy as np

    mesh = get_mesh_hint()
    if mesh is None:
        return jnp.take(table, tokens, axis=0)
    from ..distributed.sharding import dp_axes, fit

    dp = dp_axes(mesh)
    shape, dtype = table.shape, table.dtype
    tok_spec = fit(mesh, tokens.shape, *((dp,) + (None,) * (tokens.ndim - 1)))
    x_axes = (dp,) + (None,) * (tokens.ndim - 1)
    vocab_sharded = tied
    if tied:
        table_spec = fit(mesh, shape, "model", None)
        vocab_sharded = table_spec[0] is not None
        x_spec = fit(mesh, tokens.shape + (shape[1],), *x_axes, None)
    else:
        table_spec = fit(mesh, shape, None, "model")
        x_spec = fit(mesh, tokens.shape + (shape[1],), *x_axes, "model")

    dp_used = []
    t0 = tok_spec[0]
    for ax in (dp if isinstance(dp, tuple) else (dp,)):
        if t0 is not None and ax in (t0 if isinstance(t0, tuple) else (t0,)):
            dp_used.append(ax)

    def _fwd_local(tb, tok):
        if vocab_sharded:
            vloc = tb.shape[0]
            start = jax.lax.axis_index("model") * vloc
            rel = jnp.clip(tok - start, 0, vloc - 1)
            x = jnp.take(tb, rel, axis=0)
            ok = ((tok - start) >= 0) & ((tok - start) < vloc)
            x = jnp.where(ok[..., None], x, jnp.zeros((), x.dtype))
            return jax.lax.psum(x, "model")
        return jnp.take(tb, tok, axis=0)

    def _bwd_local(g, tok):
        if vocab_sharded:
            vloc = shape[0] // mesh.shape["model"]
            start = jax.lax.axis_index("model") * vloc
            rel = jnp.clip(tok - start, 0, vloc - 1)
            ok = ((tok - start) >= 0) & ((tok - start) < vloc)
            gm = jnp.where(ok[..., None], g.astype(jnp.float32), 0.0)
            dt = jnp.zeros((vloc, shape[1]), jnp.float32).at[rel].add(gm)
        else:
            dt = jnp.zeros((shape[0], g.shape[-1]), jnp.float32).at[tok].add(
                g.astype(jnp.float32))
        if dp_used:
            dt = jax.lax.psum(dt, tuple(dp_used))
        return dt.astype(dtype)

    fwd_sm = jax.shard_map(_fwd_local, mesh=mesh,
                           in_specs=(table_spec, tok_spec),
                           out_specs=x_spec, check_vma=False)
    bwd_sm = jax.shard_map(_bwd_local, mesh=mesh,
                           in_specs=(x_spec, tok_spec),
                           out_specs=table_spec, check_vma=False)

    @jax.custom_vjp
    def _lookup(t, tok):
        return fwd_sm(t, tok)

    def _vjp_fwd(t, tok):
        return fwd_sm(t, tok), tok

    def _vjp_bwd(tok, g):
        return bwd_sm(g, tok), np.zeros(tok.shape, dtype=jax.dtypes.float0)

    _lookup.defvjp(_vjp_fwd, _vjp_bwd)
    return _lookup(table, tokens)


# ------------------------------------------------------------------ init
def dense_init(key: jax.Array, shape: Tuple[int, ...],
               dtype=DEFAULT_PARAM_DTYPE, scale: Optional[float] = None
               ) -> jax.Array:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key: jax.Array, shape: Tuple[int, ...],
               dtype=DEFAULT_PARAM_DTYPE, std: float = 0.02) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def split_keys(key: jax.Array, n: int):
    return list(jax.random.split(key, n))


def count_params(tree) -> int:
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree) -> int:
    return sum(int(l.size) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))
