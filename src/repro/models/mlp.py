"""MLP variants: SwiGLU / GeGLU (gated) and plain 2-layer (GELU / squared-ReLU)."""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import ACTIVATIONS, dense_init, split_keys


def init_mlp_params(key: jax.Array, cfg: ArchConfig, d_ff: int = 0,
                    dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = split_keys(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d, f), dtype),
            "w_up": dense_init(ks[1], (d, f), dtype),
            "w_down": dense_init(ks[2], (f, d), dtype),
        }
    return {
        "w_up": dense_init(ks[0], (d, f), dtype),
        "w_down": dense_init(ks[1], (f, d), dtype),
    }


def init_adapter_params(key: jax.Array, cfg: ArchConfig,
                        dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """A rank-``mlp_adapter_rank`` LoRA on the gated MLP's gate and up
    projections: ``lora_b``'s first ``d_ff`` columns add to the gate, the
    rest to the up projection."""
    r, f = cfg.mlp_adapter_rank, cfg.d_ff
    ka, kb = split_keys(key, 2)
    return {"lora_a": dense_init(ka, (cfg.d_model, r), dtype),
            "lora_b": dense_init(kb, (r, 2 * f), dtype)}


def mlp_forward(params: Dict[str, jax.Array], x: jax.Array,
                cfg: ArchConfig,
                adapter: Optional[Dict[str, jax.Array]] = None) -> jax.Array:
    act = ACTIVATIONS[cfg.activation]
    if cfg.mlp_type in ("swiglu", "geglu"):
        gate, up = x @ params["w_gate"], x @ params["w_up"]
        if adapter is not None:
            lora = (x @ adapter["lora_a"]) @ adapter["lora_b"]
            gate = gate + lora[..., :cfg.d_ff]
            up = up + lora[..., cfg.d_ff:]
        return (act(gate) * up) @ params["w_down"]
    return act(x @ params["w_up"]) @ params["w_down"]
