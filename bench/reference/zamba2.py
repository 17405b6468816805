"""Plain reference of Zamba2 (zamba2-7b as configured in
``bench/configs/zamba2-7b.json``): float32, matmuls at ``highest``
precision, no cache, no kernels, one sequence at a time, one layer per
call so that it fits beside the served weights.  It imports nothing of
the program's model code.

Equations (transformers' ``modeling_zamba2``, the published model):

  e = embed(tokens);  x = e
  at layer i of hybrid_layer_ids, application j, block b = j mod mem_blocks:
    h = rms_in_b([x; e])                  (the concatenation: 2 d wide)
    a = attention_b(h)                    (RoPE rotate-half on whole heads,
                                           scores * (head_dim / 2)^-0.5)
    t = linear_j(mlp_b(rms_ff_b(a)))      (GELU-gated MLP; the gate/up
                                           projection plus application j's
                                           rank-r LoRA); no residual inside
  every layer i:  x = x + mamba_i(rms_i(x + t))    (t = 0 off the hybrid
                                           layers; t does not enter x)
  logits = rms_final(x) @ embed^T

Mamba2: in_proj -> [z, x B C, dt]; depthwise causal conv and SiLU over
x B C; heads h of group h // (H / G) read that group's B and C; dt =
softplus(dt + dt_bias), not floored (the published kernels pass no limit
when ``time_step_limit`` is null); the state S_h (P x N) is scanned
position by position, S = exp(dt A) S + dt x B^T, y = S C + D x; then
RMSNorm of y * silu(z) per group of d_in / G channels, and out_proj.

It reads the weights in the layout the benchmark drew them
(``mamba_blocks/in_proj``, ``shared[b]/attn/wq``, ``apps[j]/linear`` ...)
and follows the configuration's ``assumed`` departures: RMSNorm as ``x *
(1 + scale)`` with eps 1e-6 everywhere, the head tied to the embedding.

``quant="fp8"`` is the control: every matmul takes its inputs rounded to
8-bit floats (4 exponent and 3 mantissa bits; weights scaled per output
column, activations per row) and accumulates in float32, and activations
are carried in bfloat16 between matmuls: fp8 serving, the nearest precision
below the configuration's bfloat16.  The recurrence stays in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
BUCKET = 1024                # sequences are padded at the end to a multiple
ACTIVATIONS = {"gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
               "gelu": jax.nn.gelu, "silu": jax.nn.silu}


def _fp8(x, axis):
    """Round to 8-bit floats (e4m3) with a max-abs scale along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True)
                        / 240.0, 1e-30)
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _mm(h, w, quant):
    """h @ w in float32, or from fp8-rounded inputs for the control."""
    h, w = h.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "fp8":
        return (_fp8(h, -1) @ _fp8(w, -2)).astype(jnp.bfloat16)
    return h @ w


def _act(quant):
    return jnp.bfloat16 if quant == "fp8" else jnp.float32


def _rms(x, scale, groups=1):
    x32 = x.astype(jnp.float32)
    g = x32.reshape(x32.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + EPS)
    return (g.reshape(x32.shape)
            * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head.  x: (S, heads, D)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2].astype(jnp.float32), \
        x[..., D // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(x.dtype)


def _dims(arch):
    d_in = arch.ssm_expand * arch.d_model
    return dict(
        d=arch.d_model, H=arch.n_heads, K=arch.n_kv_heads,
        Dh=arch.head_dim or arch.d_model // arch.n_heads, d_in=d_in,
        Hm=d_in // arch.ssm_head_dim, P=arch.ssm_head_dim,
        G=arch.ssm_groups, N=arch.ssm_state, f=arch.d_ff,
        theta=float(arch.rope_theta),
        scale=1.0 / math.sqrt(arch.attn_scale_frac
                              * (arch.head_dim
                                 or arch.d_model // arch.n_heads)),
        act=arch.activation,
        lora=bool(arch.mlp_adapter_rank))


@functools.partial(jax.jit, static_argnames=("dims_t", "quant"))
def _mamba(blocks, i, x, t, dims_t, quant):
    """x + mamba_i(rms_i(x + t)) over one sequence.  x, t: (S, d)."""
    m = dict(dims_t)
    blk = jax.tree_util.tree_map(lambda w: w[i], blocks)
    dt_ = _act(quant)
    S = x.shape[0]
    d_in, Hm, P, G, N = m["d_in"], m["Hm"], m["P"], m["G"], m["N"]
    h = _rms(x + t, blk["ln1_scale"])
    proj = _mm(h, blk["in_proj"], quant).astype(jnp.float32)
    z, xbc, dt = (proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * G * N],
                  proj[:, 2 * d_in + 2 * G * N:])
    w = blk["conv_w"].astype(jnp.float32)                 # (Kc, C)
    Kc = w.shape[0]
    padded = jnp.pad(xbc, ((Kc - 1, 0), (0, 0)))
    conv = sum(padded[k:k + S] * w[k] for k in range(Kc))
    conv = jax.nn.silu(conv + blk["conv_b"].astype(jnp.float32))
    xs = conv[:, :d_in].reshape(S, Hm, P)
    group = np.arange(Hm) // (Hm // G)                    # head -> group
    Bm = conv[:, d_in:d_in + G * N].reshape(S, G, N)[:, group]
    Cm = conv[:, d_in + G * N:].reshape(S, G, N)[:, group]
    dt = jax.nn.softplus(dt + blk["dt_bias"])             # (S, Hm)
    A = -jnp.exp(blk["a_log"])

    def step(state, inp):                                 # state (Hm, P, N)
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * A)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((Hm, P, N), jnp.float32),
                        (xs, Bm, Cm, dt))
    y = y + blk["d_skip"][None, :, None] * xs
    y = y.reshape(S, d_in) * jax.nn.silu(z)
    y = _rms(y, blk["norm"], groups=G).astype(dt_)
    return x + _mm(y, blk["out_proj"], quant).astype(dt_)


@functools.partial(jax.jit, static_argnames=("dims_t", "quant"))
def _shared(blk, app, x, e, dims_t, quant):
    """linear_j(shared_b([x; e])) over one sequence: (S, d)."""
    m = dict(dims_t)
    dt_ = _act(quant)
    S = x.shape[0]
    H, K, Dh = m["H"], m["K"], m["Dh"]
    pos = jnp.arange(S)
    a = blk["attn"]
    h = _rms(jnp.concatenate([x, e], -1), blk["ln1_scale"])
    q = _rope(_mm(h, a["wq"], quant).reshape(S, H, Dh), pos, m["theta"])
    k = _rope(_mm(h, a["wk"], quant).reshape(S, K, Dh), pos, m["theta"])
    v = _mm(h, a["wv"], quant).reshape(S, K, Dh)
    G = H // K
    qg = q.reshape(S, K, G, Dh).astype(jnp.float32) * m["scale"]
    s = jnp.einsum("skgd,tkd->kgst", qg, k.astype(jnp.float32))
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    o = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(s, axis=-1),
                   v.astype(jnp.float32)).reshape(S, H * Dh).astype(dt_)
    h = _rms(_mm(o, a["wo"], quant).astype(dt_), blk["ln2_scale"])
    mlp = blk["mlp"]
    gate = _mm(h, mlp["w_gate"], quant).astype(jnp.float32)
    up = _mm(h, mlp["w_up"], quant).astype(jnp.float32)
    if m["lora"]:
        lora = _mm(_mm(h, app["lora_a"], quant).astype(dt_), app["lora_b"],
                   quant).astype(jnp.float32)
        gate, up = gate + lora[:, :m["f"]], up + lora[:, m["f"]:]
    y = _mm((ACTIVATIONS[m["act"]](gate) * up).astype(dt_), mlp["w_down"],
            quant).astype(dt_)
    return _mm(y, app["linear"], quant).astype(dt_)


@functools.partial(jax.jit, static_argnames=("quant",))
def _embed(table, tokens, quant):
    return table[tokens].astype(_act(quant))


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(final_scale, head, x, rows, quant):
    h = _rms(x[rows], final_scale)
    return _mm(h, head, quant).astype(jnp.float32)


def logits(params, arch, tokens: np.ndarray, rows: np.ndarray,
           quant: str = "none") -> np.ndarray:
    """Logits (len(rows), vocab) at positions ``rows`` of one sequence."""
    S = len(tokens)
    padded = np.zeros(-(-S // BUCKET) * BUCKET, np.int32)
    padded[:S] = tokens
    dims_t = tuple(sorted(_dims(arch).items()))
    app_of = {i: j for j, i in enumerate(arch.hybrid_layer_ids)}
    with jax.default_matmul_precision("highest"):
        e = _embed(params["embed"], jnp.asarray(padded), quant)
        x, zero = e, jnp.zeros_like(e)
        for i in range(arch.n_layers):
            t = zero
            if i in app_of:
                j = app_of[i]
                t = _shared(params["shared"][j % arch.mem_blocks],
                            params["apps"][j], x, e, dims_t, quant)
            x = _mamba(params["mamba_blocks"], jnp.int32(i), x, t, dims_t,
                       quant)
        head = params["embed"].T if arch.tie_embeddings else params["head"]
        padded_rows = np.zeros(-(-len(rows) // 128) * 128, np.int32)
        padded_rows[:len(rows)] = rows
        out = _head(params["final_ln_scale"], head, x,
                    jnp.asarray(padded_rows), quant)
    return np.asarray(out)[:len(rows)]
