"""Zamba2 on the CPU at a reduced size (``zamba2-7b.reduced()``: two memory
blocks applied in turn at uneven hybrid ids, grouped B/C, MLP adapters):
the plain reference against transformers' published implementation, the
program against the reference, and the ``zamba2-7b.serve`` cell served
through its own configuration, reference and count files, with the faults
that its check has to catch."""

import contextlib
import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import controls, harness, weights  # noqa: E402
from bench.reference import zamba2 as ref  # noqa: E402
from bench.run import execute  # noqa: E402
from repro.configs import get_config  # noqa: E402

# transformers derives the head size from the width, 2 * d_model / n_heads
ARCH = dataclasses.replace(get_config("zamba2-7b").reduced(), head_dim=32,
                           max_position=64)
SEED = 2 ** 31 + 17
S = 40                     # tokens compared; one 256-token chunk


def _draw():
    import jax
    return weights.make_params(ARCH, jax.random.PRNGKey(SEED))


def _tokens():
    return np.random.default_rng(SEED).integers(
        0, ARCH.vocab_size, S).astype(np.int32)


def _transformers_model(params):
    """transformers' Zamba2ForCausalLM holding ``params``: torch Linear
    weights are the transposes, norm weights 1 + scale, the conv weight
    (C, 1, K), the gate/up projection one matrix of gate then up."""
    import torch
    from transformers import Zamba2Config, Zamba2ForCausalLM
    a = ARCH
    cfg = Zamba2Config(
        vocab_size=a.vocab_size, hidden_size=a.d_model,
        num_hidden_layers=a.n_layers,
        layers_block_type=["hybrid" if i in a.hybrid_layer_ids else "mamba"
                           for i in range(a.n_layers)],
        mamba_d_state=a.ssm_state, mamba_d_conv=a.ssm_conv,
        mamba_expand=a.ssm_expand, mamba_ngroups=a.ssm_groups,
        n_mamba_heads=a.ssm_expand * a.d_model // a.ssm_head_dim,
        num_attention_heads=a.n_heads, num_key_value_heads=a.n_kv_heads,
        intermediate_size=a.d_ff, hidden_act="gelu",
        num_mem_blocks=a.mem_blocks, use_shared_attention_adapter=False,
        adapter_rank=a.mlp_adapter_rank, use_mem_rope=True,
        rope_theta=a.rope_theta, rms_norm_eps=1e-6, chunk_size=256,
        # its CPU path floors dt at time_step_min; the published kernels
        # pass no floor when time_step_limit is null, nor do the program
        # and the reference: set it below any dt drawn here
        time_step_min=1e-9, max_position_embeddings=a.max_position,
        tie_word_embeddings=True, attn_implementation="eager")
    assert (cfg.attention_head_dim, cfg.mamba_headdim) == (a.head_dim,
                                                           a.ssm_head_dim)
    model = Zamba2ForCausalLM(cfg).eval()
    p32 = __import__("jax").tree_util.tree_map(
        lambda x: np.asarray(x, np.float32), params)

    def T(x):
        return torch.tensor(np.ascontiguousarray(x))

    sd = {"model.embed_tokens.weight": T(p32["embed"]),
          "model.final_layernorm.weight": T(1 + p32["final_ln_scale"])}
    app_of = {i: j for j, i in enumerate(a.hybrid_layer_ids)}
    for i in range(a.n_layers):
        m = {k: v[i] for k, v in p32["mamba_blocks"].items()}
        pre = f"model.layers.{i}." + ("mamba_decoder." if i in app_of else "")
        sd.update({
            pre + "input_layernorm.weight": T(1 + m["ln1_scale"]),
            pre + "mamba.in_proj.weight": T(m["in_proj"].T),
            pre + "mamba.conv1d.weight": T(m["conv_w"].T[:, None, :]),
            pre + "mamba.conv1d.bias": T(m["conv_b"]),
            pre + "mamba.A_log": T(m["a_log"]),
            pre + "mamba.dt_bias": T(m["dt_bias"]),
            pre + "mamba.D": T(m["d_skip"]),
            pre + "mamba.norm.weight": T(1 + m["norm"]),
            pre + "mamba.out_proj.weight": T(m["out_proj"].T)})
        if i not in app_of:
            continue
        j = app_of[i]
        blk, app = p32["shared"][j % a.mem_blocks], p32["apps"][j]
        st = f"model.layers.{i}.shared_transformer."
        ff = st + "feed_forward."
        sd.update({f"{st}self_attn.{n}_proj.weight": T(blk["attn"]["w" + n].T)
                   for n in "qkvo"})
        sd.update({
            f"model.layers.{i}.linear.weight": T(app["linear"].T),
            st + "input_layernorm.weight": T(1 + blk["ln1_scale"]),
            st + "pre_ff_layernorm.weight": T(1 + blk["ln2_scale"]),
            ff + "gate_up_proj.weight": T(np.concatenate(
                [blk["mlp"]["w_gate"], blk["mlp"]["w_up"]], 1).T),
            ff + "down_proj.weight": T(blk["mlp"]["w_down"].T),
            ff + f"gate_up_proj_adapter_list.{j}.0.weight": T(app["lora_a"].T),
            ff + f"gate_up_proj_adapter_list.{j}.1.weight": T(app["lora_b"].T)})
    # a block's modules appear under every layer that applies it: each
    # application's adapter is loaded under its own layer's name
    assert not model.load_state_dict(sd, strict=False).unexpected_keys
    loaded = {id(model.get_parameter(n)) for n in sd}
    assert all(id(p) in loaded for p in model.parameters())
    for mod in model.modules():          # the gated norm's eps is fixed
        if type(mod).__name__ == "Zamba2RMSNormGated":
            mod.variance_epsilon = 1e-6
    return model


def test_reference_matches_transformers_zamba2():
    """float32 on both sides: they differ only in the order of float32
    sums (transformers' chunked scan against the reference's sequential
    one, its eager softmax), 3e-6 here against logits of about 0.6, so
    1e-4; the reference at fp8, the next precision down, is 0.3 away.
    The sequence stays within one 256-token chunk: transformers' CPU path
    sums the inter-chunk decay over the wrong index (its output changes
    with ``chunk_size``), which its published CUDA kernels do not do."""
    pytest.importorskip("transformers")
    import torch
    params, toks = _draw(), _tokens()
    model = _transformers_model(params)
    with torch.no_grad():
        want = model(torch.tensor(toks[None]).long()).logits[0].numpy()
    got = ref.logits(params, ARCH, toks, np.arange(S))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    fp8 = ref.logits(params, ARCH, toks, np.arange(S), quant="fp8")
    assert np.abs(fp8 - want).max() > 0.1


def test_program_forward_matches_reference():
    """The program's forward with the weights in float32 at ``highest``:
    the same equations, so 1e-4 (3e-6 here)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    params, toks = _draw(), _tokens()
    p32 = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        got = lm.forward(p32, ARCH, jnp.asarray(toks)[None])[0][0]
    want = ref.logits(params, ARCH, toks, np.arange(S))
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-4)


def test_decode_step_in_float32_matches_reference_and_a_bfloat16_state_does_not():
    """The decode step with weights and cache in float32 at ``highest``:
    every position within 1e-4 of the reference (3e-6 here).  The same
    step with its SSM state rounded to bfloat16 after every token, as a
    state carried in bfloat16 would be, reads 2e-3.  The served check's
    token gap hardly moves with such a state (a rounding of 2^-9 seldom
    changes a pick), so this limit is what holds the step to it."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    params, toks = _draw(), _tokens()
    p32 = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params)
    want = ref.logits(params, ARCH, toks, np.arange(S))
    step = jax.jit(lambda c, t, pos: lm.decode_step(p32, ARCH, c, t, pos))

    def widest(state_dtype):
        cache = jax.tree_util.tree_map(lambda c: c.astype(jnp.float32),
                                       lm.init_cache(ARCH, 1, 64))
        rows = []
        with jax.default_matmul_precision("highest"):
            for i in range(S):
                lg, cache = step(cache, jnp.asarray([toks[i]]), jnp.int32(i))
                cache["ssm"] = cache["ssm"].astype(state_dtype).astype(
                    jnp.float32)
                rows.append(np.asarray(lg[0]))
        return np.abs(np.stack(rows) - want).max()

    assert widest(jnp.float32) < 1e-4
    assert widest(jnp.bfloat16) > 1e-4


def test_served_prefill_and_decode_match_reference():
    """Prefill then decode through ``ServeEngine``'s donated cache, in the
    served bfloat16: every position's logits within 0.1 of the float32
    reference's (0.039 here; the fp8 reference is 0.32 away).  The tokens
    ``generate`` serves are the reference's best to within 0.1 at every
    position, and every call takes the cache it was given."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.serve.engine import ServeEngine
    params, toks = _draw(), _tokens()
    engine = ServeEngine(ARCH, params, max_seq=64, batch=2)
    cache = lm.init_cache(ARCH, 2, 64)
    rows = []
    for i in range(S):
        _, lg, cache = engine.step(params, cache,
                                   jnp.asarray([toks[i], toks[i]]),
                                   jnp.int32(i))
        rows.append(np.asarray(lg, np.float32))
    got = np.stack(rows, 1)
    want = ref.logits(params, ARCH, toks, np.arange(S))
    assert np.abs(got - want[None]).max() < 0.1
    P = 30
    prompts = jnp.asarray(np.stack([toks[:P], toks[:P]]))
    out = np.asarray(engine.generate(prompts, S - P))
    assert (out[:, :P] == toks[:P]).all()
    served = ref.logits(params, ARCH, out[0], np.arange(P - 1, S - 1))
    assert (served.max(-1) - served[np.arange(S - P), out[0, P:]]).max() \
        < 0.1
    engine.generate(prompts, 2)
    assert engine.stats.cache_donated == 2


# ------------------------------------------------------ the cell, tiny size
def ssm_reset():
    """Planted: the SSM state is reset to zero after every step."""
    def fault(cfg, nxt, logits, cache, new_cache, pos):
        import jax.numpy as jnp
        return nxt, logits, {**new_cache, "ssm": jnp.zeros_like(
            new_cache["ssm"])}
    return controls._decode_step_fault(fault)


def kv_unwritten():
    """Planted: no KV entry is ever written (the step returns the cache's
    K and V as it was given them)."""
    def fault(cfg, nxt, logits, cache, new_cache, pos):
        return nxt, logits, {**new_cache, "k": cache["k"], "v": cache["v"]}
    return controls._decode_step_fault(fault)


# At this size the program's widest gap read 0.007-0.013, the faults
# 0.27-0.94 and the fp8 control 0.17-0.27 over three seeds (CPU), so the
# limit here is 0.1, as in the dense serving test.
TRAFFIC = dict(batch=2, max_seq=64, pool=4, sample_sequences=4,
               prompt=dict(median=8, sigma=0.5, min=4, max=24),
               new_tokens=dict(median=12, sigma=0.5, min=8, max=24),
               gap_limit=0.1)


def run_cell(seed=2 ** 31 + 11, **traffic):
    import jax
    cell = harness.find_cell("zamba2-7b.serve")
    assert cell.config["reference"] == "zamba2" == cell.config["counts"]
    ctx = harness.Context(
        cell=cell, seed=seed, seconds=0.3, trace=False,
        t_start=time.perf_counter(),
        arch_overrides={k: v for k, v in dataclasses.asdict(ARCH).items()
                        if k != "name"},
        traffic_overrides={**TRAFFIC, **traffic})
    return execute(ctx, jax.devices()[0])


@pytest.mark.parametrize("fault", [None, controls.stale_cache, ssm_reset,
                                   kv_unwritten, "control"])
def test_zamba2_cell_is_correct_and_its_faults_are_not(fault):
    with (fault() if callable(fault) else contextlib.nullcontext()):
        line = run_cell(control=fault == "control")
    gap = line["checks"]["max_served_gap"]
    if fault is None:
        assert line["correct"], line["checks"]
        assert set(line["metrics"]) == {"setup_s", "serve_tok_s"}
        assert line["_extra"]["window_compiles"] == 0
    else:
        assert not line["correct"]
        assert gap["value"] > gap["limit"]
