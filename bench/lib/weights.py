"""Seeded weights of a Qwen3 decoder, drawn on the device in one jitted call.

The benchmark makes the weights itself, so that the plain reference can
draw the very same ones from the seed without taking anything from the
program under test.  Layout (the reference's own, stacked over layers):

  embed       (V, D)            token embedding
  lm_head     (D, V)            output head (absent when tied)
  final_norm  (D,)              RMSNorm weight (multiplicative)
  layers.attn_norm, mlp_norm (L, D);  q_norm, k_norm (L, hd)
  layers.wq (L, D, H*hd), wk / wv (L, D, K*hd), wo (L, H*hd, D)
  layers.w_gate / w_up (L, D, F), w_down (L, F, D)

Matrices are normal with the configuration's `initializer_range` as their
standard deviation; norm weights are 1 + 0.1 * normal, so that every norm
scales its input unevenly and a norm left out shows in the logits.
Everything is drawn in float32 and served in bfloat16.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

NORM_SPREAD = 0.1


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


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _blocks(n: int) -> int:
    for b in (16, 8, 4, 2):
        if n % b == 0:
            return b
    return 1


def _draw(key, shape, std, mean=0.0, dtype=jnp.bfloat16):
    """Normal(mean, std) of `shape`, drawn block by block along axis 0 so
    that no float32 copy of a whole large matrix exists at once."""
    nb = _blocks(shape[0])
    keys = jax.random.split(key, nb)
    sub = (shape[0] // nb, *shape[1:])

    def one(k):
        return (mean + std * jax.random.normal(k, sub, jnp.float32)).astype(dtype)

    return jax.lax.map(one, keys).reshape(shape)


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
            layers[name] = jax.lax.map(
                lambda k, s=shape: (1.0 + NORM_SPREAD * jax.random.normal(k, s)).astype(dtype),
                lkeys,
            )
        else:
            layers[name] = jax.lax.map(lambda k, s=shape: _draw(k, s, std, dtype=dtype), lkeys)
    w = {
        "embed": _draw(next(ks), (V, D), std, dtype=dtype),
        "final_norm": (1.0 + NORM_SPREAD * jax.random.normal(next(ks), (D,))).astype(dtype),
        "layers": layers,
    }
    head_key = next(ks)
    if not config["tie_word_embeddings"]:
        w["lm_head"] = _draw(head_key, (D, V), std, dtype=dtype)
    return w


def head(w: Dict[str, Any]) -> jax.Array:
    """(D, V) output head, tied or not."""
    return w["lm_head"] if "lm_head" in w else w["embed"].T
