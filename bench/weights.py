"""Weights drawn from ``--seed`` on the device, in one jitted call, in the
type they are served in.

The tree has the layout the program's model reads (its shapes come from
``jax.eval_shape`` of the program's initializer, which never runs); the
values are the benchmark's own, so the plain reference can rebuild them
from the seed without anything the program made.  Leaves under a
``*blocks`` key are stacked per layer and drawn one layer at a time, which
bounds the temporary float32 draw to one layer.  Biases and norm scales
get random values (the program starts them at constants), so the
comparison covers them too.  Every leaf the program's initializer makes is
drawn, of any rank: a matrix by its fan-in, and a vector no other rule
names (a bias: ``conv_b``, a LayerNorm's ``*_bias``) as ``z * 0.1``.

Mamba2's per-head leaves (``a_log``, ``dt_bias``, ``d_skip``) follow the
published Mamba2 initialisation (Dao & Gu, arXiv:2405.21060), with the
time-step range of Zamba2's configuration: A uniform in [1, 16], dt
log-uniform in [1e-3, 1e-1] and floored at 1e-4, D about 1.  The generic
draw would give every head a per-token decay near exp(-softplus(N(0, 1))),
about 0.3-0.7: the state would forget within a few tokens, so no served
token would depend on state the cache carried further than that.  With
these, the heads' time constants 1 / (dt A) at their biases spread from
under one token to a thousand.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

A_RANGE = (1.0, 16.0)          # Mamba2: A = -exp(a_log), A uniform here
DT_RANGE = (1e-3, 1e-1)        # Zamba2: time_step_min, time_step_max
DT_FLOOR = 1e-4                # Zamba2: time_step_floor


def shapes(arch):
    from repro.models import lm
    return jax.eval_shape(lambda k: lm.init_params(arch, k),
                          jax.random.PRNGKey(0))


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def stacked(path) -> bool:
    return any(str(getattr(p, "key", "")).endswith("blocks") for p in path)


def draw_leaf(name: str, key, shape, dtype):
    """One leaf (one layer's slice for stacked leaves)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        v = z * 0.02
    elif name.endswith("_scale") or name == "norm":
        v = z * 0.1
    elif name in ("bq", "bk", "bv"):
        v = z * 0.1
    elif name == "a_log":
        v = jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
    elif name == "dt_bias":                 # softplus^-1 of dt
        lo, hi = (math.log(t) for t in DT_RANGE)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), DT_FLOOR)
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "d_skip":
        v = 1.0 + z * 0.1
    elif len(shape) == 1:                   # other biases
        v = z * 0.1
    else:                                   # projections: 1/sqrt(fan_in)
        v = z / math.sqrt(shape[-2])
    return v.astype(dtype)


def make_params_fn(tree):
    """A function key -> params with the structure and dtypes of ``tree``
    (a pytree of ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def make(key):
        leaves = []
        for i, (path, sd) in enumerate(flat):
            name, k = _leaf_name(path), jax.random.fold_in(key, i)
            if stacked(path):
                keys = jax.random.split(k, sd.shape[0])
                leaves.append(jax.lax.map(
                    lambda kk: draw_leaf(name, kk, sd.shape[1:], sd.dtype),
                    keys))
            else:
                leaves.append(draw_leaf(name, k, sd.shape, sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make


def make_params(arch, key):
    """The cell's weights, drawn on the default device."""
    return jax.jit(make_params_fn(shapes(arch)))(key)
