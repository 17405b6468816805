"""GQA/MQA/MHA attention with a memory-efficient (flash-style) JAX path and
a KV-cache decode path.

The chunked formulation below is the pure-JAX twin of the Pallas flash
kernel in ``repro.kernels.flash_attention``: it never materializes the full
(S, T) score matrix, which is what lets ``prefill_32k`` compile within HBM.
``repro.kernels.ops`` dispatches to the Pallas kernel on TPU.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import apply_rope, dense_init, split_keys

NEG_INF = -2.0 ** 30


# ----------------------------------------------------------------- params
def init_attn_params(key: jax.Array, cfg: ArchConfig,
                     dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    d, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    a = cfg.attn_in_dim            # what q/k/v read; wo writes d_model
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (a, H * Dh), dtype),
        "wk": dense_init(ks[1], (a, K * Dh), dtype),
        "wv": dense_init(ks[2], (a, K * Dh), dtype),
        "wo": dense_init(ks[3], (H * Dh, d), dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H * Dh,), dtype)
        p["bk"] = jnp.zeros((K * Dh,), dtype)
        p["bv"] = jnp.zeros((K * Dh,), dtype)
    return p


def score_scale(cfg: ArchConfig) -> float:
    """What q.k is multiplied by: (attn_scale_frac * head_dim)^-0.5."""
    return 1.0 / math.sqrt(cfg.attn_scale_frac * cfg.resolved_head_dim)


# ------------------------------------------------- chunked causal attention
def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      *, causal: bool = True, chunk: int = 1024,
                      q_chunk: int = 512, q_offset: int = 0,
                      scale: Optional[float] = None) -> jax.Array:
    """Flash-style attention blocked in BOTH directions (never materializes
    more than a (bq, bk) score tile per head group).

    q: (B, S, H, D); k/v: (B, T, K, D) with H = G*K.  ``q_offset``: absolute
    position of q[0] (decode / chunked prefill).  Outer scan over q tiles,
    inner scan over KV tiles with the running (max, sum, acc) triple — the
    same loop structure as the Pallas kernel grid.  ``scale`` multiplies
    the scores (default D^-0.5).
    """
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    bq = min(q_chunk, S)
    nq = -(-S // bq)
    pad_q = nq * bq - S
    qf = (q.astype(jnp.float32) * scale).reshape(B, S, K, G, D)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    qc = jnp.moveaxis(qf.reshape(B, nq, bq, K, G, D), 1, 0)

    bk = min(chunk, T)
    nk = -(-T // bk)
    pad_t = nk * bk - T
    if pad_t:
        k = jnp.pad(k, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    kc = jnp.moveaxis(k.reshape(B, nk, bk, K, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, bk, K, D), 1, 0)

    def q_block(_, q_in):
        qb, qi = q_in                              # (B, bq, K, G, D)
        q_pos = q_offset + qi * bq + jnp.arange(bq)

        def body(carry, inputs):
            m, l, acc = carry
            kb, vb, c_idx = inputs
            kv_pos = c_idx * bk + jnp.arange(bk)
            s = jnp.einsum("bskgd,btkd->bskgt", qb, kb.astype(jnp.float32))
            bad = (kv_pos >= T)[None, :]
            if causal:
                bad = bad | (kv_pos[None, :] > q_pos[:, None])
            s = jnp.where(bad[None, :, None, None, :], NEG_INF, s)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bskgt,btkd->bskgd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, bq, K, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, bq, K, G), jnp.float32)
        a0 = jnp.zeros((B, bq, K, G, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (kc, vc, jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, None, (qc, jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * bq, K, G, D)[:, :S]
    return out.reshape(B, S, H, D)


# ----------------------------------------------------------- full forward
def attn_forward(params: Dict[str, jax.Array], x: jax.Array,
                 cos: jax.Array, sin: jax.Array, cfg: ArchConfig,
                 *, q_offset: int = 0, kv_chunk: int = 1024) -> jax.Array:
    """Causal self-attention over a full sequence (train / prefill)."""
    B, S, d = x.shape
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.attn_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, K, Dh)
    v = v.reshape(B, S, K, Dh)
    rd = int(Dh * cfg.rotary_fraction)
    if rd:
        pos = q_offset + jnp.arange(S)
        q = apply_rope(q, cos, sin, positions=pos, rotary_dim=rd)
        k = apply_rope(k, cos, sin, positions=pos, rotary_dim=rd)
    out = chunked_attention(q, k, v, causal=True, chunk=kv_chunk,
                            q_offset=q_offset, scale=score_scale(cfg))
    return out.reshape(B, S, H * Dh) @ params["wo"]


# ------------------------------------------------------------------ decode
def attn_decode(params: Dict[str, jax.Array], x: jax.Array,
                cache_k: jax.Array, cache_v: jax.Array, pos: jax.Array,
                cos: jax.Array, sin: jax.Array, cfg: ArchConfig,
                *, layer: Optional[jax.Array] = None, kv_chunk: int = 8192
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a KV cache.

    x: (B, 1, attn_in_dim); cache_k/v: (B, S_max, K, Dh); pos: scalar
    current length.
    With ``layer``, cache_k/v are the whole stacked cache (L, B, S_max, K,
    Dh): the new entry is written at (layer, :, pos) and the chunks are read
    from that layer of it, so a layer scan that carries the cache updates
    it in place.
    Returns (out (B,1,d), new_cache_k, new_cache_v).
    """
    B, _, d = x.shape
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    S_max = cache_k.shape[-3]
    lead = () if layer is None else (layer,)    # index of the layer, if any
    ones = (1,) * len(lead)

    def project(name):
        # bias added to the float32 sum and rounded once: what the TPU did
        # when it fused the add into the matmul, kept now that the in-place
        # cache write takes the add into its own fusion
        y = jnp.dot(x, params["w" + name], preferred_element_type=jnp.float32)
        if cfg.attn_bias:
            y = y + params["b" + name]
        return y.astype(x.dtype)

    q = project("q").reshape(B, 1, H, Dh)
    k = project("k").reshape(B, 1, K, Dh)
    v = project("v").reshape(B, 1, K, Dh)
    rd = int(Dh * cfg.rotary_fraction)
    if rd:
        pvec = jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q, cos, sin, positions=pvec, rotary_dim=rd)
        k = apply_rope(k, cos, sin, positions=pvec, rotary_dim=rd)
    at = lead + (0, pos, 0, 0)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype).reshape(ones + k.shape), at)
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype).reshape(ones + v.shape), at)

    # flash-decoding: stream KV chunks with a running softmax so the score
    # tensor never exceeds (B, K, G, chunk) — bounded at 500k-token caches
    G = H // K
    qf = (q.astype(jnp.float32) * score_scale(cfg)).reshape(B, K, G, Dh)
    bk = min(kv_chunk, S_max)
    while S_max % bk:          # keep chunks aligned without padding copies
        bk //= 2
    nk = S_max // bk

    def body(carry, ci):
        m, l, acc = carry
        # dynamic slices view the cache in place — no transposed copy of a
        # multi-GiB buffer
        start, size = lead + (0, ci * bk, 0, 0), ones + (B, bk, K, Dh)
        kb = jax.lax.dynamic_slice(cache_k, start, size).reshape(B, bk, K, Dh)
        vb = jax.lax.dynamic_slice(cache_v, start, size).reshape(B, bk, K, Dh)
        t_pos = ci * bk + jnp.arange(bk)
        s = jnp.einsum("bkgd,btkd->bkgt", qf, kb.astype(jnp.float32))
        s = jnp.where((t_pos > pos)[None, None, None, :], NEG_INF, s)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgt,btkd->bkgd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G), jnp.float32)
    a0 = jnp.zeros((B, K, G, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nk))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.reshape(B, 1, H * Dh).astype(x.dtype) @ params["wo"]
    return out, cache_k, cache_v
