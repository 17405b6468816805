"""Mamba-2 (SSD) block and the shared chunked linear-recurrence engine.

The SSD recurrence  S_t = a_t * S_{t-1} + k_t v_t^T,  y_t = S_t^T q_t  is
computed chunkwise (Mamba-2 paper §6): intra-chunk quadratic term with a
decay mask + inter-chunk state carried by a ``lax.scan``.  The carried state
is (B, H, P, N) — constant in sequence length, which is what makes
``long_500k`` feasible.  The same engine drives the mLSTM in
``repro.models.xlstm`` (state N == P, gate-derived decays).

A Pallas TPU kernel for the intra-chunk term lives in
``repro.kernels.ssd_scan``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import dense_init, split_keys


# ---------------------------------------------------------------------------
def chunked_linear_scan(a: jax.Array, k: jax.Array, v: jax.Array,
                        q: jax.Array, *, chunk: int = 256,
                        initial_state: jax.Array = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Chunked scan of S_t = a_t S_{t-1} + k_t v_t^T ;  y_t = S_t^T q_t.

    a: (B, S, H) per-step decay in (0, 1]; k, q: (B, S, H, N);
    v: (B, S, H, P).  Returns y: (B, S, H, P) and final state (B, H, N, P).
    """
    B, S, H, N = k.shape
    P = v.shape[-1]
    Q = min(chunk, S)
    n_chunks = -(-S // Q)
    pad = n_chunks * Q - S
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    def to_chunks(x):
        return jnp.moveaxis(
            x.reshape((B, n_chunks, Q) + x.shape[2:]), 1, 0)

    ac, kc, vc, qc = map(to_chunks, (a, k, v, q))    # (n, B, Q, ...)

    if initial_state is None:
        initial_state = jnp.zeros((B, H, N, P), jnp.float32)

    def body(S_prev, inp):
        a_b, k_b, v_b, q_b = inp                      # (B, Q, H, ...)
        la = jnp.log(jnp.maximum(a_b.astype(jnp.float32), 1e-37))
        cum = jnp.cumsum(la, axis=1)                  # (B, Q, H)
        # intra-chunk: mask[i, j] = prod_{j < t <= i} a_t  (i >= j)
        seg = cum[:, :, None, :] - cum[:, None, :, :]     # (B, Q, Q, H)
        iq = jnp.arange(Q)
        causal = (iq[:, None] >= iq[None, :])
        # masked before the exponential: above the diagonal seg grows
        # without bound, and exp's gradient there would be inf times 0
        mask = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        scores = jnp.einsum("bihn,bjhn->bijh", q_b.astype(jnp.float32),
                            k_b.astype(jnp.float32)) * mask
        y_intra = jnp.einsum("bijh,bjhp->bihp", scores,
                             v_b.astype(jnp.float32))
        # inter-chunk: decay from chunk start to position i (inclusive)
        dec_in = jnp.exp(cum)                          # (B, Q, H)
        y_inter = jnp.einsum("bihn,bhnp->bihp",
                             q_b.astype(jnp.float32) * dec_in[..., None],
                             S_prev)
        # chunk state update: decay each contribution to chunk end
        dec_out = jnp.exp(cum[:, -1:, :] - cum)        # (B, Q, H)
        S_chunk = jnp.einsum("bihn,bihp->bhnp",
                             k_b.astype(jnp.float32) * dec_out[..., None],
                             v_b.astype(jnp.float32))
        S_new = S_prev * jnp.exp(cum[:, -1, :])[:, :, None, None] + S_chunk
        return S_new, y_intra + y_inter

    S_fin, yc = jax.lax.scan(body, initial_state, (ac, kc, vc, qc))
    y = jnp.moveaxis(yc, 0, 1).reshape(B, n_chunks * Q, H, P)[:, :S]
    return y, S_fin


def linear_scan_step(state: jax.Array, a: jax.Array, k: jax.Array,
                     v: jax.Array, q: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Single-token recurrence step (decode).

    state: (B, H, N, P); a: (B, H); k, q: (B, H, N); v: (B, H, P).
    Returns (y (B, H, P), new_state)."""
    state = state * a[..., None, None].astype(jnp.float32) \
        + jnp.einsum("bhn,bhp->bhnp", k.astype(jnp.float32),
                     v.astype(jnp.float32))
    y = jnp.einsum("bhnp,bhn->bhp", state, q.astype(jnp.float32))
    return y, state


# ---------------------------------------------------------------------------
def _dims(cfg: ArchConfig):
    """(d_in, H, P, G, N, conv channels): heads h of group h // (H // G)
    read that group's B and C."""
    d_in = cfg.ssm_expand * cfg.d_model
    P, G, N = cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    return d_in, d_in // P, P, G, N, d_in + 2 * G * N


def init_mamba2_params(key: jax.Array, cfg: ArchConfig,
                       dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    d = cfg.d_model
    d_in, H, _, _, _, conv_ch = _dims(cfg)
    ks = split_keys(key, 4)
    return {
        # order: [z (d_in), x (d_in), B (G*N), C (G*N), dt (H)]
        "in_proj": dense_init(ks[0], (d, d_in + conv_ch + H), dtype),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv, conv_ch), dtype,
                             scale=0.5),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "a_log": jnp.zeros((H,), jnp.float32),              # A = -exp(a_log)
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),       # softplus bias
        "d_skip": jnp.ones((H,), jnp.float32),
        "norm": jnp.zeros((d_in,), dtype),
        "out_proj": dense_init(ks[2], (d_in, d), dtype),
    }


def _split_proj(proj: jax.Array, cfg: ArchConfig):
    d_in, _, _, _, _, conv_ch = _dims(cfg)
    return (proj[..., :d_in], proj[..., d_in:d_in + conv_ch],
            proj[..., d_in + conv_ch:])


def _heads(xbc: jax.Array, cfg: ArchConfig):
    """Split the convolved channels into x (..., H, P) and the B and C each
    head reads, (..., H, N)."""
    d_in, H, P, G, N, _ = _dims(cfg)
    lead = xbc.shape[:-1]

    def per_head(m):
        m = m.reshape(lead + (G, 1, N))
        return jnp.broadcast_to(m, lead + (G, H // G, N)).reshape(
            lead + (H, N))

    return (xbc[..., :d_in].reshape(lead + (H, P)),
            per_head(xbc[..., d_in:d_in + G * N]),
            per_head(xbc[..., d_in + G * N:]))


def _gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                cfg: ArchConfig) -> jax.Array:
    """RMSNorm of y * silu(z) taken per group of d_in / G channels, in
    float32.  y: (..., d_in) float32."""
    _, _, _, G, _, _ = _dims(cfg)
    g = y * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(g.shape[:-1] + (G, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + 1e-6)
    return g.reshape(y.shape) * (1.0 + scale.astype(jnp.float32))


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv along S.  xbc: (B, S, C); w: (K, C)."""
    K = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i] for i in range(K))
    return jax.nn.silu(out + b)


def mamba2_forward(params: Dict[str, jax.Array], x: jax.Array,
                   cfg: ArchConfig, *, chunk: int = 256) -> jax.Array:
    """Full-sequence Mamba-2 block.  x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    d_in = _dims(cfg)[0]
    z, xbc, dt = _split_proj(x @ params["in_proj"], cfg)
    xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
    xs, k, q = _heads(xbc, cfg)                          # (B, S, H, ...)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"])            # (B, S, H)
    A = -jnp.exp(params["a_log"])                        # (H,)
    a = jnp.exp(dt * A)                                  # decay in (0,1]
    v = xs * dt[..., None]
    y, _ = chunked_linear_scan(a, k, v, q, chunk=chunk)
    y = y + xs.astype(jnp.float32) * params["d_skip"][None, None, :, None]
    y = _gated_norm(y.reshape(B, S, d_in), z, params["norm"], cfg)
    return y.astype(x.dtype) @ params["out_proj"]


def init_mamba2_cache(cfg: ArchConfig, batch: int, layers: int):
    """Every layer's SSM state (float32) and convolution window."""
    _, H, P, _, N, conv_ch = _dims(cfg)
    return {
        "ssm": jnp.zeros((layers, batch, H, N, P), jnp.float32),
        "conv": jnp.zeros((layers, batch, cfg.ssm_conv - 1, conv_ch),
                          jnp.bfloat16),
    }


def mamba2_decode(params: Dict[str, jax.Array], x: jax.Array, cache: Dict,
                  cfg: ArchConfig) -> Tuple[jax.Array, Dict]:
    """One-token step of one layer.  x: (B, 1, d); cache: this layer's
    ``ssm`` (B, H, N, P) and ``conv`` (B, K - 1, C)."""
    B = x.shape[0]
    d_in = _dims(cfg)[0]
    z, xbc, dt = _split_proj(x @ params["in_proj"], cfg)
    # conv over the cached window + current token
    win = jnp.concatenate([cache["conv"], xbc.astype(cache["conv"].dtype)],
                          axis=1)                        # (B, K, C)
    w = params["conv_w"]
    conv = jax.nn.silu((win * w[None]).sum(axis=1) + params["conv_b"])
    xs, k, q = _heads(conv, cfg)                         # (B, H, ...)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["a_log"])
    a = jnp.exp(dt * A)                                  # (B, H)
    v = xs * dt[..., None]
    y, new_state = linear_scan_step(cache["ssm"], a, k, v, q)
    y = y + xs.astype(jnp.float32) * params["d_skip"][None, :, None]
    y = _gated_norm(y.reshape(B, 1, d_in), z, params["norm"], cfg)
    new_cache = {"ssm": new_state, "conv": win[:, 1:]}
    return y.astype(x.dtype) @ params["out_proj"], new_cache
