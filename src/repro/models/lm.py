"""Language model assembly for all assigned architectures.

Three block patterns share one LM skeleton (embed -> blocks -> norm -> head):

* ``attn``              — dense / MoE / VLM / audio transformers; layers are
                          stacked and scanned (``lax.scan`` keeps HLO small).
* ``mamba_shared_attn`` — zamba2: Mamba2 backbone, one *shared* attention
                          block (own KV per application) every ``attn_every``
                          layers.
* ``xlstm``             — mLSTM stacks with an sLSTM block every
                          ``slstm_every`` layers.

Functional API:
  init_params(cfg, key)                       -> params pytree
  forward(params, cfg, tokens, frontend)      -> logits
  loss_fn(params, cfg, batch)                 -> scalar loss
  init_cache(cfg, batch, max_seq)             -> decode cache pytree
  decode_step(params, cfg, cache, token, pos) -> (logits, new cache)

Weights use remat-friendly ``lax.scan`` over stacked layers; activation
checkpointing policy is chosen by the launch layer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..configs.base import ArchConfig
from . import attention, mamba2, mlp as mlp_mod, moe as moe_mod, xlstm
from .common import (dense_init, embed_init, embed_lookup, layer_norm,
                     rms_norm, rope_frequencies, shard_hint, split_keys)


# ---------------------------------------------------------------- norms
def _norm(params_block: Dict[str, jax.Array], name: str, x: jax.Array,
          cfg: ArchConfig) -> jax.Array:
    if cfg.norm == "layer":
        return layer_norm(x, params_block[f"{name}_scale"],
                          params_block[f"{name}_bias"])
    return rms_norm(x, params_block[f"{name}_scale"])


def _init_norm(cfg: ArchConfig, name: str, dtype=jnp.bfloat16):
    p = {f"{name}_scale": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.norm == "layer":
        p[f"{name}_scale"] = jnp.ones((cfg.d_model,), dtype)
        p[f"{name}_bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


# ----------------------------------------------------------- transformer blk
def _init_attn_block(key: jax.Array, cfg: ArchConfig, dtype=jnp.bfloat16):
    ks = split_keys(key, 2)
    p = {"attn": attention.init_attn_params(ks[0], cfg, dtype)}
    p.update(_init_norm(cfg, "ln1", dtype))
    p.update(_init_norm(cfg, "ln2", dtype))
    if cfg.is_moe:
        p["moe"] = moe_mod.init_moe_params(ks[1], cfg, dtype)
    elif cfg.d_ff:
        p["mlp"] = mlp_mod.init_mlp_params(ks[1], cfg, dtype=dtype)
    return p


def _attn_block_fwd(block, x, cos, sin, cfg, q_offset=0):
    aux = jnp.zeros((), jnp.float32)
    h = _norm(block, "ln1", x, cfg)
    x = x + attention.attn_forward(block["attn"], h, cos, sin, cfg,
                                   q_offset=q_offset)
    h = _norm(block, "ln2", x, cfg)
    if cfg.is_moe:
        out, aux = moe_mod.moe_forward(block["moe"], h, cfg)
        x = x + out
    elif cfg.d_ff:
        x = x + mlp_mod.mlp_forward(block["mlp"], h, cfg)
    return x, aux


def _attn_block_decode(block, x, ck, cv, pos, cos, sin, cfg, layer=None):
    h = _norm(block, "ln1", x, cfg)
    out, ck, cv = attention.attn_decode(block["attn"], h, ck, cv, pos,
                                        cos, sin, cfg, layer=layer)
    x = x + out
    h = _norm(block, "ln2", x, cfg)
    if cfg.is_moe:
        out, _ = moe_mod.moe_forward(block["moe"], h, cfg)
        x = x + out
    elif cfg.d_ff:
        x = x + mlp_mod.mlp_forward(block["mlp"], h, cfg)
    return x, ck, cv


# ----------------------------------------------------------------- init all
def init_params(cfg: ArchConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Dict[str, Any]:
    ks = split_keys(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
    }
    params.update({f"final_{k}": v
                   for k, v in _init_norm(cfg, "ln", dtype).items()})
    if not cfg.tie_embeddings:
        params["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                    dtype)

    if cfg.block_pattern == "attn":
        layer_keys = jnp.stack(split_keys(ks[2], cfg.n_layers))
        params["blocks"] = jax.vmap(
            lambda k: _init_attn_block(k, cfg, dtype))(layer_keys)
    elif cfg.block_pattern == "mamba_shared_attn":
        layer_keys = jnp.stack(split_keys(ks[2], cfg.n_layers))
        def mamba_block(k):
            p = mamba2.init_mamba2_params(k, cfg, dtype)
            p.update(_init_norm(cfg, "ln1", dtype))
            return p
        params["mamba_blocks"] = jax.vmap(mamba_block)(layer_keys)
        params["shared_attn"] = _init_attn_block(ks[3], cfg, dtype)
    elif cfg.block_pattern == "xlstm":
        n_s = cfg.n_layers // cfg.slstm_every if cfg.slstm_every else 0
        n_m = cfg.n_layers - n_s
        mkeys = jnp.stack(split_keys(ks[2], n_m))
        def m_block(k):
            p = xlstm.init_mlstm_params(k, cfg, dtype)
            p.update(_init_norm(cfg, "ln1", dtype))
            return p
        params["mlstm_blocks"] = jax.vmap(m_block)(mkeys)
        if n_s:
            skeys = jnp.stack(split_keys(ks[3], n_s))
            def s_block(k):
                p = xlstm.init_slstm_params(k, cfg, dtype)
                p.update(_init_norm(cfg, "ln1", dtype))
                return p
            params["slstm_blocks"] = jax.vmap(s_block)(skeys)
    else:
        raise ValueError(cfg.block_pattern)

    if cfg.frontend == "vision":
        params["frontend_proj"] = dense_init(ks[4], (cfg.d_model, cfg.d_model),
                                             dtype)
    return params


# ----------------------------------------------------------------- forward
def _rope_tables(cfg: ArchConfig, max_pos: int):
    rd = int(cfg.resolved_head_dim * cfg.rotary_fraction)
    return rope_frequencies(cfg.resolved_head_dim, max_pos,
                            theta=cfg.rope_theta, rotary_dim=rd)


def forward(params: Dict[str, Any], cfg: ArchConfig, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None,
            remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S_text).  Returns (logits (B, S_total, V), aux_loss)."""
    x = embed_lookup(params["embed"], tokens, tied=cfg.tie_embeddings)
    if cfg.frontend == "vision" and frontend_embeds is not None:
        fe = frontend_embeds.astype(x.dtype) @ params["frontend_proj"]
        x = jnp.concatenate([fe, x], axis=1)
    elif frontend_embeds is not None:      # audio conditioning frames
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    B, S, d = x.shape
    x = shard_hint(x, "dp", None, "model")
    cos, sin = _rope_tables(cfg, S)
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.block_pattern == "attn":
        def body(carry, block):
            h, aux = carry
            h, a = _attn_block_fwd(block, h, cos, sin, cfg)
            h = shard_hint(h, "dp", None, "model")
            return (h, aux + a), None
        if remat:
            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                         params["blocks"])
    elif cfg.block_pattern == "mamba_shared_attn":
        x = _hybrid_forward(params, cfg, x, cos, sin, remat=remat)
    else:
        x = _xlstm_forward(params, cfg, x, remat=remat)

    x = (rms_norm(x, params["final_ln_scale"]) if cfg.norm == "rms"
         else layer_norm(x, params["final_ln_scale"], params["final_ln_bias"]))
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = shard_hint(x @ head, "dp", None, "model")
    return logits, aux_total


def _hybrid_forward(params, cfg, x, cos, sin, remat=False):
    """zamba2: shared attn block every ``attn_every`` Mamba2 layers."""
    L, k = cfg.n_layers, cfg.attn_every
    blocks = params["mamba_blocks"]

    def shared(h):
        return _attn_block_fwd(params["shared_attn"], h, cos, sin, cfg)[0]

    def body(h, blk):
        hn = _norm(blk, "ln1", h, cfg)
        h = h + mamba2.mamba2_forward(blk, hn, cfg)
        return shard_hint(h, "dp", None, "model"), None

    if remat:
        shared = jax.checkpoint(shared)
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    done = 0
    while done < L:
        x = shared(x)
        take = min(k, L - done)
        chunk = jax.tree_util.tree_map(lambda w: w[done:done + take], blocks)
        x, _ = jax.lax.scan(body, x, chunk)
        done += take
    return x


def _xlstm_forward(params, cfg, x, remat=False):
    L = cfg.n_layers
    period = cfg.slstm_every or (L + 1)
    n_s = L // period
    m_per_group = period - 1
    mi, si = 0, 0
    mblocks = params["mlstm_blocks"]
    done = 0
    while done < L:
        take = min(m_per_group, L - done - (1 if si < n_s else 0))
        if take > 0:
            chunk = jax.tree_util.tree_map(
                lambda w: w[mi:mi + take], mblocks)
            def body(h, blk):
                hn = _norm(blk, "ln1", h, cfg)
                h = h + xlstm.mlstm_forward(blk, hn, cfg)
                return shard_hint(h, "dp", None, "model"), None
            if remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            x, _ = jax.lax.scan(body, x, chunk)
            mi += take
            done += take
        if si < n_s and done < L:
            blk = jax.tree_util.tree_map(lambda w: w[si],
                                         params["slstm_blocks"])
            def s_apply(h):
                hn = _norm(blk, "ln1", h, cfg)
                return h + xlstm.slstm_forward(blk, hn, cfg)
            if remat:
                s_apply = jax.checkpoint(s_apply)
            x = s_apply(x)
            si += 1
            done += 1
    return x


# -------------------------------------------------------------------- loss
def loss_fn(params: Dict[str, Any], cfg: ArchConfig,
            batch: Dict[str, jax.Array], remat: bool = False
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch.get("frontend"), remat=remat)
    # align: frontend tokens carry no loss
    n_front = logits.shape[1] - batch["tokens"].shape[1]
    logits = logits[:, n_front:]
    targets = batch["labels"]
    logits = logits[:, :-1].astype(jnp.float32)
    targets = targets[:, 1:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold).mean()
    loss = nll + 0.01 * aux
    return loss, {"nll": nll, "aux": aux}


# ------------------------------------------------------------------- decode
def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               kv_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """``kv_dtype=jnp.float8_e4m3fn`` halves KV-cache HBM (keys/values are
    dequantized to fp32 inside attention; per-value fp8 e4m3 is the
    standard low-risk KV compression)."""
    K, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    if cfg.block_pattern == "attn":
        shape = (cfg.n_layers, batch, max_seq, K, Dh)
        return {"k": jnp.zeros(shape, kv_dtype),
                "v": jnp.zeros(shape, kv_dtype)}
    if cfg.block_pattern == "mamba_shared_attn":
        n_apps = -(-cfg.n_layers // cfg.attn_every)
        m = mamba2.init_mamba2_cache(cfg, batch)
        return {
            "mamba": jax.tree_util.tree_map(
                lambda z: jnp.broadcast_to(
                    z[None], (cfg.n_layers,) + z.shape), m),
            "k": jnp.zeros((n_apps, batch, max_seq, K, Dh), kv_dtype),
            "v": jnp.zeros((n_apps, batch, max_seq, K, Dh), kv_dtype),
        }
    # xlstm
    period = cfg.slstm_every or (cfg.n_layers + 1)
    n_s = cfg.n_layers // period
    n_m = cfg.n_layers - n_s
    mc = xlstm.init_mlstm_cache(cfg, batch)
    cache = {"mlstm": jax.tree_util.tree_map(
        lambda z: jnp.broadcast_to(z[None], (n_m,) + z.shape), mc)}
    if n_s:
        sc = xlstm.init_slstm_cache(cfg, batch)
        cache["slstm"] = jax.tree_util.tree_map(
            lambda z: jnp.broadcast_to(z[None], (n_s,) + z.shape), sc)
    return cache


def _argument_layout(x: jax.Array) -> jax.Array:
    """Pin ``x`` to the layout the default device gives an argument of its
    shape.  Left free, the TPU compiler lays a carried cache out as its
    one-entry update prefers, and relayouts the whole cache on entering and
    leaving the layer scan; pinned, a donated cache is updated where it lies."""
    dev = jax.devices()[0]
    layout = dev.client.get_default_layout(x.dtype, x.shape, dev)
    return with_layout_constraint(x, Layout.from_pjrt_layout(layout))


def decode_step(params: Dict[str, Any], cfg: ArchConfig,
                cache: Dict[str, Any], token: jax.Array, pos: jax.Array
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """token: (B,) int32; pos: scalar int32 (current sequence length).

    Returns (logits (B, V), new cache)."""
    x = embed_lookup(params["embed"], token,
                     tied=cfg.tie_embeddings)[:, None, :]   # (B, 1, d)
    max_pos = cfg.max_position
    cos, sin = _rope_tables(cfg, max_pos)

    if cfg.block_pattern == "attn":
        # the stacked cache is carried, and each layer writes its one new
        # entry in place: with the cache donated, a step copies none of it
        def body(carry, inputs):
            h, ck, cv = carry
            blk, layer = inputs
            h, ck, cv = _attn_block_decode(blk, h, ck, cv, pos, cos, sin, cfg,
                                           layer=layer)
            return (h, _argument_layout(ck), _argument_layout(cv)), None
        (x, ks, vs), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], jnp.arange(cfg.n_layers)))
        new_cache = {"k": ks, "v": vs}
    elif cfg.block_pattern == "mamba_shared_attn":
        x, new_cache = _hybrid_decode(params, cfg, cache, x, pos, cos, sin)
    else:
        x, new_cache = _xlstm_decode(params, cfg, cache, x)

    x = (rms_norm(x, params["final_ln_scale"]) if cfg.norm == "rms"
         else layer_norm(x, params["final_ln_scale"], params["final_ln_bias"]))
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = shard_hint((x @ head)[:, 0], None, "model")
    return logits, new_cache


def _hybrid_decode(params, cfg, cache, x, pos, cos, sin):
    L, k = cfg.n_layers, cfg.attn_every
    blocks = params["mamba_blocks"]
    new_m = []
    ks, vs = [], []
    done, app = 0, 0
    while done < L:
        x, ck, cv = _attn_block_decode(
            params["shared_attn"], x, cache["k"][app], cache["v"][app],
            pos, cos, sin, cfg)
        ks.append(ck)
        vs.append(cv)
        app += 1
        take = min(k, L - done)
        chunk = jax.tree_util.tree_map(lambda w: w[done:done + take], blocks)
        mcache = jax.tree_util.tree_map(lambda w: w[done:done + take],
                                        cache["mamba"])
        def body(h, inputs):
            blk, mc = inputs
            hn = _norm(blk, "ln1", h, cfg)
            out, mc2 = mamba2.mamba2_decode(blk, hn, mc, cfg)
            return h + out, mc2
        x, mc_new = jax.lax.scan(body, x, (chunk, mcache))
        new_m.append(mc_new)
        done += take
    mamba_cache = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *new_m)
    return x, {"mamba": mamba_cache, "k": jnp.stack(ks), "v": jnp.stack(vs)}


def _xlstm_decode(params, cfg, cache, x):
    L = cfg.n_layers
    period = cfg.slstm_every or (L + 1)
    n_s = L // period
    m_per_group = period - 1
    mblocks = params["mlstm_blocks"]
    new_m, new_s = [], []
    mi, si, done = 0, 0, 0
    while done < L:
        take = min(m_per_group, L - done - (1 if si < n_s else 0))
        if take > 0:
            chunk = jax.tree_util.tree_map(lambda w: w[mi:mi + take], mblocks)
            mcache = jax.tree_util.tree_map(lambda w: w[mi:mi + take],
                                            cache["mlstm"])
            def body(h, inputs):
                blk, mc = inputs
                hn = _norm(blk, "ln1", h, cfg)
                out, mc2 = xlstm.mlstm_decode(blk, hn, mc, cfg)
                return h + out, mc2
            x, mc_new = jax.lax.scan(body, x, (chunk, mcache))
            new_m.append(mc_new)
            mi += take
            done += take
        if si < n_s and done < L:
            blk = jax.tree_util.tree_map(lambda w: w[si],
                                         params["slstm_blocks"])
            sc = jax.tree_util.tree_map(lambda w: w[si], cache["slstm"])
            hn = _norm(blk, "ln1", x, cfg)
            out, sc2 = xlstm.slstm_decode(blk, hn, sc, cfg)
            x = x + out
            new_s.append(sc2)
            si += 1
            done += 1
    out_cache = {"mlstm": jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *new_m)}
    if new_s:
        out_cache["slstm"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0), *new_s)
    return x, out_cache
