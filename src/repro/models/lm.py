"""Language model assembly for all assigned architectures.

Three block patterns share one LM skeleton (embed -> blocks -> norm -> head):

* ``attn``              — dense / MoE / VLM / audio transformers; layers are
                          stacked and scanned (``lax.scan`` keeps HLO small).
* ``mamba_shared_attn`` — zamba2: Mamba2 backbone; at each of
                          ``hybrid_ids`` one of ``mem_blocks`` *shared*
                          attention+MLP blocks, in turn (own KV, ``linear``
                          and adapter per application), feeds that layer's
                          Mamba2 input.
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
from ..core.spans import scope
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


def _init_norm(cfg: ArchConfig, name: str, dtype=jnp.bfloat16,
               width: Optional[int] = None):
    width = width or cfg.d_model
    p = {f"{name}_scale": jnp.zeros((width,), dtype)}
    if cfg.norm == "layer":
        p[f"{name}_scale"] = jnp.ones((width,), dtype)
        p[f"{name}_bias"] = jnp.zeros((width,), dtype)
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


# ------------------------------------------------------------ hybrid blocks
def _init_shared_block(key: jax.Array, cfg: ArchConfig, dtype=jnp.bfloat16):
    ks = split_keys(key, 2)
    p = {"attn": attention.init_attn_params(ks[0], cfg, dtype),
         "mlp": mlp_mod.init_mlp_params(ks[1], cfg, dtype=dtype)}
    p.update(_init_norm(cfg, "ln1", dtype, width=cfg.attn_in_dim))
    p.update(_init_norm(cfg, "ln2", dtype))
    return p


def _init_application(key: jax.Array, cfg: ArchConfig, dtype=jnp.bfloat16):
    """What one application of a shared block owns: the ``linear`` into the
    residual stream and the MLP adapter."""
    ks = split_keys(key, 2)
    p = {"linear": dense_init(ks[0], (cfg.d_model, cfg.d_model), dtype)}
    if cfg.mlp_adapter_rank:
        p.update(mlp_mod.init_adapter_params(ks[1], cfg, dtype))
    return p


def _shared_apply(params, cfg, j, x, e, attend):
    """Application ``j``: linear_j(shared_{j mod mem_blocks}([x; e])), with
    no residual inside the block.  ``attend(attn_params, h)`` is the
    attention (over the sequence or against the cache): (out, extra)."""
    blk, app = params["shared"][j % cfg.mem_blocks], params["apps"][j]
    h = jnp.concatenate([x, e], axis=-1)
    out, extra = attend(blk["attn"], _norm(blk, "ln1", h, cfg))
    h = mlp_mod.mlp_forward(blk["mlp"], _norm(blk, "ln2", out, cfg), cfg,
                            adapter=app if cfg.mlp_adapter_rank else None)
    return h @ app["linear"], extra


def _hybrid_segments(cfg: ArchConfig):
    """(application or None, first layer, end): the Mamba2 layers run in
    order, application j feeding the input of layer ``hybrid_ids[j]``."""
    ids = cfg.hybrid_ids
    if not ids:
        return [(None, 0, cfg.n_layers)]
    ends = list(ids[1:]) + [cfg.n_layers]
    lead = [(None, 0, ids[0])] if ids[0] else []
    return lead + [(j, i, end) for j, (i, end) in enumerate(zip(ids, ends))]


def _layer(tree, i):
    """Layer ``i`` of a stacked tree, read where it lies."""
    return jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False), tree)


def _mamba_layers(layer_fn, carry, t, start, end):
    """Layers start..end-1 in a loop: ``layer_fn(carry, t, i)``; ``t`` (the
    application's output) feeds layer ``start``'s input, zeros the rest."""
    def body(i, c):
        carry, t = c
        return layer_fn(carry, t, i), jnp.zeros_like(t)
    return jax.lax.fori_loop(start, end, body, (carry, t))[0]


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
        params["shared"] = [_init_shared_block(k, cfg, dtype)
                            for k in split_keys(ks[3], cfg.mem_blocks)]
        params["apps"] = [_init_application(k, cfg, dtype) for k in
                          split_keys(ks[5], len(cfg.hybrid_ids))]
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
    """zamba2: x <- x + mamba_i(norm(x + t_i)), t_i the output of the shared
    block applied at layer i (zero at the other layers)."""
    e = x                                       # the embedding output

    def shared(j, h):
        return _shared_apply(params, cfg, j, h, e, lambda a, hn: (
            attention.attn_forward(a, hn, cos, sin, cfg), None))[0]

    def layer(h, t, i):
        blk = _layer(params["mamba_blocks"], i)
        hn = _norm(blk, "ln1", h + t, cfg)
        return shard_hint(h + mamba2.mamba2_forward(blk, hn, cfg),
                          "dp", None, "model")

    if remat:
        shared = jax.checkpoint(shared, static_argnums=0)
        layer = jax.checkpoint(layer,
                               policy=jax.checkpoint_policies.nothing_saveable)
    for j, start, end in _hybrid_segments(cfg):
        t = jnp.zeros_like(x) if j is None else shared(j, x)
        x = _mamba_layers(layer, x, t, start, end)
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
        shape = (len(cfg.hybrid_ids), batch, max_seq, K, Dh)  # per application
        return {**mamba2.init_mamba2_cache(cfg, batch, cfg.n_layers),
                "k": jnp.zeros(shape, kv_dtype),
                "v": jnp.zeros(shape, kv_dtype)}
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
    """The hybrid step carries the stacked KV, SSM and conv caches: each
    application writes its one KV entry, each Mamba2 layer its own state
    slice, and layer weights are read in the loop, so with the cache
    donated a step copies none of them."""
    e = x
    ks, vs = cache["k"], cache["v"]

    def layer(c, t, i):
        h, ssm, conv = c
        with scope("mamba2"):
            blk = _layer(params["mamba_blocks"], i)
            hn = _norm(blk, "ln1", h + t, cfg)
            out, mc = mamba2.mamba2_decode(
                blk, hn, _layer({"ssm": ssm, "conv": conv}, i), cfg)
            ssm, conv = (_argument_layout(jax.lax.dynamic_update_index_in_dim(
                whole, mc[name], i, 0)) for name, whole in
                (("ssm", ssm), ("conv", conv)))
            return h + out, ssm, conv

    carry = (x, cache["ssm"], cache["conv"])
    for j, start, end in _hybrid_segments(cfg):
        t = jnp.zeros_like(x)
        if j is not None:
            with scope("shared_block"):
                t, (ks, vs) = _shared_apply(
                    params, cfg, j, carry[0], e,
                    lambda a, hn: _attend_cached(a, hn, ks, vs, pos, cos, sin,
                                                 cfg, j))
        carry = _mamba_layers(layer, carry, t, start, end)
    x, ssm, conv = carry
    return x, {"ssm": ssm, "conv": conv, "k": ks, "v": vs}


def _attend_cached(a, h, ks, vs, pos, cos, sin, cfg, j):
    out, ks, vs = attention.attn_decode(a, h, ks, vs, pos, cos, sin, cfg,
                                        layer=j)
    return out, (_argument_layout(ks), _argument_layout(vs))


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
