"""Counts of a Zamba2 hybrid (``block_pattern`` ``mamba_shared_attn``):
Mamba2 layers, and shared attention+MLP blocks applied at
``hybrid_layer_ids``, each application with its own ``linear`` and MLP
adapter.

The least HBM bytes of a decode step: every weight once, except that a
shared block is read once per application; the batch's embedding rows and
the tied head's whole table; per Mamba2 layer the float32 SSM state read
and written and the convolution window read with one new column written;
per application the KV cache read up to the position and one new entry
written.  FLOPs: 2 per multiply-accumulate of the matmuls, the
depthwise convolution, 6 per element of the state (decay, update and
read-out), and QK^T and AV over pos + 1 keys.

``mamba_*`` and ``shared_block_*`` are the two parts that the
``unimem.mamba2`` and ``unimem.shared_block`` scopes of the decode step
cover; ``decode_*`` is the whole step.  ``arch`` is the configuration as
the benchmark builds it (``repro.configs.base.ArchConfig``).
"""

from __future__ import annotations

BF16, F32 = 2, 4


def _mamba_dims(arch):
    d_in = arch.ssm_expand * arch.d_model
    H = d_in // arch.ssm_head_dim
    G, N = arch.ssm_groups, arch.ssm_state
    return d_in, H, arch.ssm_head_dim, N, d_in + 2 * G * N


def _head_dim(arch) -> int:
    return arch.head_dim or arch.d_model // arch.n_heads


def n_applications(arch) -> int:
    return len(arch.hybrid_ids)


# ------------------------------------------------------------ Mamba2 layers
def mamba_layer_matmul_params(arch) -> int:
    d = arch.d_model
    d_in, H, _, _, C = _mamba_dims(arch)
    return d * (d_in + C + H) + d_in * d            # in_proj, out_proj


def mamba_layer_weight_bytes(arch) -> int:
    """bf16 matrices, convolution and norms; float32 A, dt bias and D."""
    d = arch.d_model
    d_in, H, _, _, C = _mamba_dims(arch)
    bf16 = (mamba_layer_matmul_params(arch) + (arch.ssm_conv + 1) * C
            + d_in + d)
    return bf16 * BF16 + 3 * H * F32


def mamba_flops(arch, batch: int) -> float:
    """Every Mamba2 layer of one decode step."""
    _, H, P, N, C = _mamba_dims(arch)
    per_token = (2 * mamba_layer_matmul_params(arch) + 2 * arch.ssm_conv * C
                 + 6 * H * N * P)
    return float(arch.n_layers * batch * per_token)


def mamba_bytes(arch, batch: int) -> float:
    _, H, P, N, C = _mamba_dims(arch)
    state = 2 * batch * H * N * P * F32                 # read and written
    conv = batch * arch.ssm_conv * C * BF16             # K - 1 read, 1 written
    return float(arch.n_layers * (mamba_layer_weight_bytes(arch) + state
                                  + conv))


# ------------------------------------------------ shared-block applications
def shared_block_matmul_params(arch) -> int:
    """One application: its block's attention and MLP, its adapter and its
    ``linear``."""
    d, a, f = arch.d_model, arch.attn_in_dim, arch.d_ff
    H, K, Dh = arch.n_heads, arch.n_kv_heads, _head_dim(arch)
    attn = a * (H + 2 * K) * Dh + H * Dh * d
    return attn + 3 * d * f + arch.mlp_adapter_rank * (d + 2 * f) + d * d


def shared_block_flops(arch, batch: int, pos: int) -> float:
    """Every application of one decode step at position ``pos``."""
    H, Dh = arch.n_heads, _head_dim(arch)
    per_token = 2 * shared_block_matmul_params(arch) + 4 * H * Dh * (pos + 1)
    return float(n_applications(arch) * batch * per_token)


def shared_block_bytes(arch, batch: int, pos: int) -> float:
    weights = (shared_block_matmul_params(arch) + arch.attn_in_dim
               + arch.d_model) * BF16                    # and its two norms
    kv_entry = 2 * arch.n_kv_heads * _head_dim(arch) * BF16   # k and v
    kv = batch * kv_entry * (pos + 1)
    return float(n_applications(arch) * (weights + kv))


# --------------------------------------------------------------- whole step
def decode_flops(arch, batch: int, pos: int) -> float:
    head = 2 * arch.d_model * arch.vocab_size * batch
    return mamba_flops(arch, batch) + shared_block_flops(arch, batch, pos) \
        + float(head)


def decode_bytes(arch, batch: int, pos: int) -> float:
    d, V = arch.d_model, arch.vocab_size
    rest = (V * d + batch * d + d) * BF16     # tied head, rows, final norm
    return mamba_bytes(arch, batch) + shared_block_bytes(arch, batch, pos) \
        + float(rest)
