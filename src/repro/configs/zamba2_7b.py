"""zamba2-7b [hybrid]: Zamba2-7B-Instruct at its published widths.

81 Mamba2 layers, d_model 3584: 112 heads of 64, d_state 64, 2 groups of
B/C, conv 4, expand 2.  Layers 6, 11, 17, ..., 77 (13) also take the
output of one of two shared attention+MLP blocks, applied in turn: each
reads concat(hidden, embedding), 7168 wide, through 32 heads of 224 with
RoPE and scores scaled by (head_dim / 2)^-0.5, then a GELU-gated MLP 14336
wide whose gate/up projection has a rank-128 LoRA of its own in each
application, and each application has its own 3584x3584 ``linear``.
Vocab 32000, context 4096, head tied to the embedding.
[https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json;
arXiv:2411.15242]
"""

from .base import ArchConfig

CONFIG = ArchConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    activation="gelu_exact",
    mlp_type="geglu",
    ssm_state=64,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv=4,
    ssm_groups=2,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    mem_blocks=2,
    mlp_adapter_rank=128,
    attn_scale_frac=0.5,
    block_pattern="mamba_shared_attn",
    supports_long_context=True,
    tie_embeddings=True,
    max_position=4096,
)
