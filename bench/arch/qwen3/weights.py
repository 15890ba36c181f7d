"""Seeded weights of a Qwen3 decoder, drawn on the device in one jitted call.

Layout (the reference's own, stacked over layers):

  embed       (V, D)            token embedding
  lm_head     (D, V)            output head (absent when tied)
  final_norm  (D,)              RMSNorm weight (multiplicative)
  layers.attn_norm, mlp_norm (L, D);  q_norm, k_norm (L, hd)
  layers.wq (L, D, H*hd), wk / wv (L, D, K*hd), wo (L, H*hd, D)
  layers.w_gate / w_up (L, D, F), w_down (L, F, D)

Matrices and norm weights are drawn as `bench/lib/weights.py` says.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.lib.weights import draw, draw_norm


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    return {
        "L": int(config["num_hidden_layers"]),
        "D": int(config["hidden_size"]),
        "H": int(config["num_attention_heads"]),
        "K": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "F": int(config["intermediate_size"]),
        "V": int(config["vocab_size"]),
    }


def make_weights(config: Dict[str, Any], key: jax.Array, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The weights of `config` from `key`; trace under `jax.jit`."""
    d = dims(config)
    L, D, H, K, hd, F, V = (d[k] for k in ("L", "D", "H", "K", "hd", "F", "V"))
    std = float(config["initializer_range"])
    ks = iter(jax.random.split(key, 16))
    layer_shapes = {
        "attn_norm": ((D,), "norm"),
        "mlp_norm": ((D,), "norm"),
        "q_norm": ((hd,), "norm"),
        "k_norm": ((hd,), "norm"),
        "wq": ((D, H * hd), "mat"),
        "wk": ((D, K * hd), "mat"),
        "wv": ((D, K * hd), "mat"),
        "wo": ((H * hd, D), "mat"),
        "w_gate": ((D, F), "mat"),
        "w_up": ((D, F), "mat"),
        "w_down": ((F, D), "mat"),
    }
    layers = {}
    for name, (shape, kind) in layer_shapes.items():
        lkeys = jax.random.split(next(ks), L)
        if kind == "norm":
            layers[name] = jax.lax.map(lambda k, s=shape: draw_norm(k, s, dtype), lkeys)
        else:
            layers[name] = jax.lax.map(lambda k, s=shape: draw(k, s, std, dtype=dtype), lkeys)
    w = {
        "embed": draw(next(ks), (V, D), std, dtype=dtype),
        "final_norm": draw_norm(next(ks), (D,), dtype),
        "layers": layers,
    }
    head_key = next(ks)
    if not config["tie_word_embeddings"]:
        w["lm_head"] = draw(head_key, (D, V), std, dtype=dtype)
    return w


def head(w: Dict[str, Any]) -> jax.Array:
    """(D, V) output head, tied or not."""
    return w["lm_head"] if "lm_head" in w else w["embed"].T
