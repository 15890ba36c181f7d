"""The program's view of a Qwen3 configuration and of its weights."""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from repro.configs.base import ModelConfig

from .weights import dims


def model_config(config: Dict[str, Any]) -> ModelConfig:
    d = dims(config)
    return ModelConfig(
        name=config["name"],
        family="dense",
        n_layers=d["L"],
        d_model=d["D"],
        n_heads=d["H"],
        n_kv_heads=d["K"],
        d_ff=d["F"],
        vocab_size=d["V"],
        head_dim=d["hd"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        qk_norm=True,
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype="bfloat16",
        param_dtype="bfloat16",
    )


def to_program(w: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The benchmark's weights in the program's parameter layout: layers
    stacked (L, 1, ...), heads split out of the projections, and norm
    weights stored as offsets from 1 (the program scales by 1 + w)."""
    lw = w["layers"]
    L, D, _ = lw["wq"].shape
    hd = lw["q_norm"].shape[-1]

    def off(g):  # exact in bf16: g is 1 + a multiple of 2^-7 near 1
        return (g.astype(jnp.float32) - 1.0).astype(g.dtype)

    def one(x):
        return x[:, None]

    p = {
        "embed": {"tok": w["embed"]},
        "final_norm": off(w["final_norm"]),
        "decoder": {
            "ln1": one(off(lw["attn_norm"])),
            "ln2": one(off(lw["mlp_norm"])),
            "attn": {
                "wq": one(lw["wq"].reshape(L, D, -1, hd)),
                "wk": one(lw["wk"].reshape(L, D, -1, hd)),
                "wv": one(lw["wv"].reshape(L, D, -1, hd)),
                "wo": one(lw["wo"].reshape(L, -1, hd, D)),
                "q_norm": one(off(lw["q_norm"])),
                "k_norm": one(off(lw["k_norm"])),
            },
            "mlp": {
                "w_gate": one(lw["w_gate"]),
                "w_up": one(lw["w_up"]),
                "w_down": one(lw["w_down"]),
            },
        },
    }
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"]
    return p
