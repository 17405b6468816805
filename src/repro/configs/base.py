"""Architecture and shape configuration.

Every assigned architecture is an :class:`ArchConfig`; the four assigned
input shapes are :class:`ShapeConfig`.  ``reduced()`` yields the small
same-family variant used by CPU smoke tests (full configs are exercised only
through the dry-run with ShapeDtypeStructs).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str            # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense|moe|hybrid|vlm|audio|ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # default: d_model // n_heads
    activation: str = "silu"
    mlp_type: str = "swiglu"                # swiglu|geglu|mlp
    norm: str = "rms"                       # rms|layer
    attn_bias: bool = False
    rope_theta: float = 10000.0
    rotary_fraction: float = 1.0            # chatglm3: 0.5 (2d RoPE)
    tie_embeddings: bool = False
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    moe_shared_experts: int = 0
    moe_capacity_factor: float = 1.25
    # SSM / hybrid
    ssm_state: int = 0                      # mamba2 d_state
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_groups: int = 1                     # mamba2 ngroups: heads share B/C
    attn_every: int = 0                     # hybrid: shared attn block period
    hybrid_layer_ids: Tuple[int, ...] = ()  # hybrid: layers a shared block
                                            # feeds (default: every attn_every)
    mem_blocks: int = 1                     # hybrid: shared blocks, in turn
    mlp_adapter_rank: int = 0               # per-application LoRA on gate/up
    attn_scale_frac: float = 1.0            # scores * (frac * head_dim)^-0.5
    slstm_every: int = 0                    # xlstm: sLSTM block period
    block_pattern: str = "attn"             # attn|mamba_shared_attn|xlstm
    # modality frontend stub
    frontend: Optional[str] = None          # None|vision|audio
    frontend_tokens: int = 0
    # shape applicability
    supports_long_context: bool = False     # sub-quadratic -> run long_500k
    max_position: int = 544 * 1024

    def __post_init__(self):
        # a configuration file gives the ids as a list; keep the config hashable
        object.__setattr__(self, "hybrid_layer_ids",
                           tuple(self.hybrid_layer_ids))

    # ------------------------------------------------------------- derived
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def hybrid_ids(self) -> Tuple[int, ...]:
        """Layers whose Mamba2 input a shared-block application feeds."""
        ids = self.hybrid_layer_ids or range(0, self.n_layers, self.attn_every)
        return tuple(i for i in ids if i < self.n_layers)

    @property
    def attn_in_dim(self) -> int:
        """Width the attention projections read: a hybrid's shared block
        reads concat(hidden, embedding)."""
        if self.block_pattern == "mamba_shared_attn":
            return 2 * self.d_model
        return self.d_model

    @property
    def is_moe(self) -> bool:
        return self.moe_experts > 0

    def n_params(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, hd = self.d_model, self.resolved_head_dim
        p = self.vocab_size * d            # embed
        if not self.tie_embeddings:
            p += d * self.vocab_size       # head
        per_layer = 0
        if self.block_pattern == "mamba_shared_attn":
            d_in = self.ssm_expand * d
            n_h = d_in // self.ssm_head_dim
            conv_ch = d_in + 2 * self.ssm_groups * self.ssm_state
            mamba = (d * (d_in + conv_ch + n_h)          # in_proj: z, xBC, dt
                     + (self.ssm_conv + 1) * conv_ch     # conv weight, bias
                     + 3 * n_h + d_in + d_in * d + d)    # A, dt bias, D, norm,
            p += mamba * self.n_layers + d               # out_proj, ln; final
            a, f = self.attn_in_dim, self.d_ff
            attn = (a * self.n_heads * hd + 2 * a * self.n_kv_heads * hd
                    + self.n_heads * hd * d)
            p += self.mem_blocks * (a + attn + d + 3 * d * f)
            per_app = d * d + self.mlp_adapter_rank * (d + 2 * f)
            return p + per_app * len(self.hybrid_ids)
        if self.block_pattern == "xlstm":
            d_in = self.ssm_expand * d
            H = self.n_heads
            n_s = self.n_layers // self.slstm_every if self.slstm_every else 0
            n_m = self.n_layers - n_s
            mlstm = (d * (3 * d_in + 2 * H) + d * d_in   # in_proj + o_gate
                     + d_in + d_in * d + d)              # norm + out + ln1
            slstm = (d * 4 * d + 4 * d * d // H          # w_gates + r_gates
                     + d + d * d + d)                    # norm + out + ln1
            p += mlstm * n_m + slstm * n_s
            return p
        q = d * self.n_heads * hd
        kv = 2 * d * self.n_kv_heads * hd
        o = self.n_heads * hd * d
        attn = q + kv + o
        if self.is_moe:
            expert = 3 * d * self.moe_d_ff if self.mlp_type != "mlp" \
                else 2 * d * self.moe_d_ff
            mlp = (self.moe_experts + self.moe_shared_experts) * expert \
                + d * self.moe_experts    # router
        else:
            mlp = 3 * d * self.d_ff if self.mlp_type != "mlp" \
                else 2 * d * self.d_ff
        p += (attn + mlp + 2 * d) * self.n_layers
        return p

    def n_active_params(self) -> int:
        """Active parameters per token (MoE: only routed experts count)."""
        if not self.is_moe:
            return self.n_params()
        d = self.d_model
        expert = 3 * d * self.moe_d_ff if self.mlp_type != "mlp" \
            else 2 * d * self.moe_d_ff
        inactive = (self.moe_experts - self.moe_top_k) * expert * self.n_layers
        return self.n_params() - inactive

    def shape_applicable(self, shape: ShapeConfig) -> Tuple[bool, str]:
        if shape.name == "long_500k" and not self.supports_long_context:
            return False, "pure full-attention arch: quadratic at 500k (skip)"
        return True, ""

    def reduced(self) -> "ArchConfig":
        """Small same-family config for CPU smoke tests.  A hybrid with
        explicit ``hybrid_layer_ids`` keeps uneven ids, two memory blocks
        applied in turn, grouped B/C and its adapters."""
        n_layers = 2
        if self.block_pattern == "mamba_shared_attn":
            n_layers = (5 if self.hybrid_layer_ids
                        else max(2, min(4, (self.attn_every or 2) + 1)))
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=n_layers,
            d_model=64,
            n_heads=4,
            n_kv_heads=min(4, max(1, self.n_kv_heads * 4 // self.n_heads)),
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab_size=128,
            moe_experts=4 if self.is_moe else 0,
            moe_top_k=2 if self.is_moe else 0,
            moe_d_ff=32 if self.is_moe else 0,
            moe_shared_experts=min(1, self.moe_shared_experts),
            ssm_state=16 if self.ssm_state else 0,
            ssm_head_dim=16 if self.ssm_state else 64,
            ssm_groups=min(2, self.ssm_groups),
            attn_every=2 if self.attn_every else 0,
            hybrid_layer_ids=(1, 3, 4) if self.hybrid_layer_ids else (),
            mem_blocks=min(2, self.mem_blocks),
            mlp_adapter_rank=8 if self.mlp_adapter_rank else 0,
            slstm_every=2 if self.slstm_every else 0,
            frontend_tokens=4 if self.frontend else 0,
            max_position=1024,
        )
