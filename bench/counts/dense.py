"""Counts of a dense GQA decoder (``block_pattern`` ``attn``, gated MLP):
the count module of every configuration that names none.

``arch`` is any object with the configuration's fields (``n_layers``,
``d_model``, ``n_heads``, ...), as in ``bench/configs/<name>.json``'s
``arch``.
"""

from __future__ import annotations

BF16 = 2


def head_dim(arch) -> int:
    return arch.head_dim or arch.d_model // arch.n_heads


# ------------------------------------------------------------ dense (GQA)
def dense_layer_matmul_params(arch) -> int:
    """Multiply-accumulate weights of one attention + gated-MLP layer."""
    d, H, K, Dh = arch.d_model, arch.n_heads, arch.n_kv_heads, head_dim(arch)
    return d * H * Dh + 2 * d * K * Dh + H * Dh * d + 3 * d * arch.d_ff


def dense_layer_params(arch) -> int:
    """Every weight of one layer: matmuls, q/k/v bias, two norm scales."""
    H, K, Dh = arch.n_heads, arch.n_kv_heads, head_dim(arch)
    bias = (H * Dh + 2 * K * Dh) if arch.attn_bias else 0
    return dense_layer_matmul_params(arch) + bias + 2 * arch.d_model


def dense_params(arch) -> int:
    """Embedding, layers, final norm and (untied) head."""
    d, V = arch.d_model, arch.vocab_size
    head = 0 if arch.tie_embeddings else d * V
    return V * d + arch.n_layers * dense_layer_params(arch) + d + head


def dense_decode_flops(arch, batch: int, pos: int) -> float:
    """One decode step of ``batch`` rows at position ``pos`` (0-based: the
    step attends to positions 0..pos): 2 FLOPs per multiply-accumulate of
    the layers and the head, plus QK^T and AV over pos + 1 keys."""
    H, Dh = arch.n_heads, head_dim(arch)
    per_token = 2 * (arch.n_layers * dense_layer_matmul_params(arch)
                     + arch.d_model * arch.vocab_size)
    attn = arch.n_layers * 4 * H * Dh * (pos + 1)
    return float(batch * (per_token + attn))


def dense_decode_bytes(arch, batch: int, pos: int,
                       kv_bytes: int = BF16, w_bytes: int = BF16) -> float:
    """Least HBM bytes of one decode step: every weight once (only the
    ``batch`` embedding rows it looks up), the KV cache up to ``pos``
    read, and one new KV entry per layer and row written."""
    d, V = arch.d_model, arch.vocab_size
    weights = (dense_params(arch) - V * d + batch * d) * w_bytes
    kv_entry = 2 * arch.n_kv_heads * head_dim(arch) * kv_bytes   # k and v
    kv = arch.n_layers * batch * kv_entry * (pos + 1)
    return float(weights + kv)


# the names ``bench/metrics/_decode.py`` reads from every count module
decode_flops = dense_decode_flops
decode_bytes = dense_decode_bytes
