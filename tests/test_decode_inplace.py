"""The attention decode step updates its KV cache in place: the layer scan
carries the stacked cache and writes one entry per layer, and the serving
engine donates the cache to the step.

The carried formulation must compute what the scan over per-layer cache
slices computed, bit for bit, and the engine's donation must really engage.
Compile-only checks for the chip are in ``test_chip_compile.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import RuntimeConfig, UnimemRuntime
from repro.core.tiers import TPU_V5E
from repro.models import lm
from repro.models.common import embed_lookup, layer_norm, rms_norm
from repro.serve.engine import ServeEngine, build_decode_step

B, MAX_SEQ, PROMPT = 2, 16, 5


def decode_step_over_slices(params, cfg, cache, token, pos):
    """The step as it was before the cache was carried: the layer scan takes
    each layer's cache slice as an input and stacks the updated slices as
    its output."""
    x = embed_lookup(params["embed"], token, tied=cfg.tie_embeddings)[:, None]
    cos, sin = lm._rope_tables(cfg, cfg.max_position)

    def body(h, inputs):
        blk, ck, cv = inputs
        h, ck, cv = lm._attn_block_decode(blk, h, ck, cv, pos, cos, sin, cfg)
        return h, (ck, cv)

    x, (ks, vs) = jax.lax.scan(body, x,
                               (params["blocks"], cache["k"], cache["v"]))
    x = (rms_norm(x, params["final_ln_scale"]) if cfg.norm == "rms"
         else layer_norm(x, params["final_ln_scale"], params["final_ln_bias"]))
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return (x @ head)[:, 0], {"k": ks, "v": vs}


def tiny(name):
    return dataclasses.replace(get_config(name).reduced(), max_position=64)


def run(step, params, cache, prompts):
    """Prefill the prompts, then decode greedily up to position
    ``MAX_SEQ - 1``; returns every step's logits and the final cache."""
    tok, out = prompts[:, 0], []
    for pos in range(MAX_SEQ):
        logits, cache = step(params, cache, tok, jnp.int32(pos))
        out.append(np.asarray(logits))
        tok = (prompts[:, pos + 1] if pos + 1 < PROMPT
               else jnp.argmax(logits, -1).astype(jnp.int32))
    return np.stack(out), cache


@pytest.mark.parametrize("name", ["chatglm3-6b", "gemma-2b",
                                  "moonshot-v1-16b-a3b"])
def test_carried_cache_matches_scan_over_slices(name):
    """Dense (qkv bias, half-dim RoPE), MQA and MoE attention: logits of a
    whole prefill and decode, and the final cache, are bit-identical."""
    cfg = tiny(name)
    assert cfg.block_pattern == "attn"
    assert cfg.is_moe == (name == "moonshot-v1-16b-a3b")
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    prompts = jax.random.randint(jax.random.PRNGKey(2), (B, PROMPT), 0,
                                 cfg.vocab_size, jnp.int32)
    carried = jax.jit(lambda p, c, t, i: lm.decode_step(p, cfg, c, t, i),
                      donate_argnums=(1,))
    over_slices = jax.jit(
        lambda p, c, t, i: decode_step_over_slices(p, cfg, c, t, i))
    got, got_cache = run(carried, params, lm.init_cache(cfg, B, MAX_SEQ),
                         prompts)
    want, want_cache = run(over_slices, params,
                           lm.init_cache(cfg, B, MAX_SEQ), prompts)
    np.testing.assert_array_equal(got, want)
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_cache[leaf]),
                                      np.asarray(want_cache[leaf]))
    # every position was written, none left at its initial zero
    assert (np.abs(np.asarray(got_cache["k"], np.float32))
            .sum(axis=(0, 1, 3, 4)) > 0).all()


def test_donated_step_aliases_both_cache_leaves():
    cfg = tiny("chatglm3-6b")
    spec = lambda t: jax.tree_util.tree_map(   # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    params = spec(jax.eval_shape(lambda: lm.init_params(
        cfg, jax.random.PRNGKey(0))))
    cache = spec(jax.eval_shape(lambda: lm.init_cache(cfg, B, MAX_SEQ)))
    token = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = jax.jit(build_decode_step(cfg), donate_argnums=(1,)).lower(
        params, cache, token, pos).compile()
    cache_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree_util.tree_leaves(cache))
    alias = compiled.memory_analysis().alias_size_in_bytes
    assert alias >= cache_bytes
    plain = jax.jit(build_decode_step(cfg)).lower(
        params, cache, token, pos).compile()
    assert plain.memory_analysis().alias_size_in_bytes == 0


def serve_twice(engine, prompts):
    return [np.asarray(engine.generate(prompts, 6)) for _ in range(2)]


def test_engine_donates_its_cache_and_serves_the_same_tokens():
    """Two calls in a row with a runtime attached: the tokens equal those of
    an engine without one, and both calls' first step consumed its cache."""
    cfg = tiny("chatglm3-6b")
    params = lm.init_params(cfg, jax.random.PRNGKey(3))
    prompts = jax.random.randint(jax.random.PRNGKey(4), (B, 7), 0,
                                 cfg.vocab_size, jnp.int32)
    rt = UnimemRuntime(TPU_V5E, RuntimeConfig(backend="jax_async"))
    tiered = ServeEngine(cfg, params, max_seq=MAX_SEQ, batch=B, runtime=rt)
    plain = ServeEngine(cfg, params, max_seq=MAX_SEQ, batch=B)
    got, want = serve_twice(tiered, prompts), serve_twice(plain, prompts)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(got[0], got[1])
    assert tiered.stats.cache_donated == 2
    assert plain.stats.cache_donated == 2


def test_cache_donated_reads_zero_without_donation():
    cfg = tiny("chatglm3-6b")
    engine = ServeEngine(cfg, lm.init_params(cfg, jax.random.PRNGKey(0)),
                         max_seq=MAX_SEQ, batch=B)
    engine.step = jax.jit(build_decode_step(cfg))       # no donation
    engine.generate(jnp.zeros((B, 3), jnp.int32), 2)
    assert engine.stats.cache_donated == 0
