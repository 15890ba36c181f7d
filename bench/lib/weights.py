"""Seeded weights drawn on the device, and weights rounded to a lower precision.

The benchmark makes the weights itself, so that the plain reference can
draw the very same ones from the seed without taking anything from the
program under test.  Which matrices a model has, and in what layout, is
its architecture binding's (`bench/arch/`); the draws are shared:

  matrices     normal with the configuration's `initializer_range` as
               their standard deviation (`draw`)
  norm weights 1 + 0.1 * normal (`draw_norm`), so that every norm scales
               its input unevenly and a norm left out shows in the logits

Everything is drawn in float32 and served in bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_SPREAD = 0.1


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


def draw(key, shape, std, mean=0.0, dtype=jnp.bfloat16):
    """Normal(mean, std) of `shape`, drawn block by block along axis 0 so
    that no float32 copy of a whole large matrix exists at once."""
    nb = _blocks(shape[0])
    keys = jax.random.split(key, nb)
    sub = (shape[0] // nb, *shape[1:])

    def one(k):
        return (mean + std * jax.random.normal(k, sub, jnp.float32)).astype(dtype)

    return jax.lax.map(one, keys).reshape(shape)


def draw_norm(key, shape, dtype=jnp.bfloat16):
    """A norm weight, 1 + NORM_SPREAD * normal."""
    return (1.0 + NORM_SPREAD * jax.random.normal(key, shape)).astype(dtype)


def quantize(w: jax.Array, quant: str) -> jax.Array:
    """Round a (..., in, out) matrix as a lower-precision server would:
    to int8 (symmetric, one scale per output column) or to float8 e4m3,
    and back to bfloat16.  The control of the comparison computes with
    weights rounded so."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return (jnp.round(wf / scale) * scale).astype(jnp.bfloat16)
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        return ((wf / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale).astype(
            jnp.bfloat16
        )
    raise ValueError(f"unknown quant {quant!r}")
