"""Faults planted in the timed path, to show that `correct` catches them.

  token_altered    the sampler returns each row's second-best token
  state_unchanged  a decode step returns its KV cache as it got it

`planted(name)` swaps the engine's function for the broken one and puts
it back on exit.  The CPU tests and `calibrate.py --fault` (on the chip,
at the cell's own size) use the same code.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

FAULTS = ("token_altered", "state_unchanged")


def _second_best(logits, keys, steps, temperature):
    masked = logits.at[jnp.arange(logits.shape[0]), jnp.argmax(logits, -1)].set(-jnp.inf)
    return jnp.argmax(masked, -1).astype(jnp.int32)


def _stale_cache(decode_step):
    def step(p, cfg, tokens, cache, cache_len):
        logits, _ = decode_step(p, cfg, tokens, cache, cache_len)
        return logits, cache

    return step


@contextlib.contextmanager
def planted(name: str):
    import repro.serve.continuous as cont

    attr, broken = {
        "token_altered": ("sample_tokens", lambda f: _second_best),
        "state_unchanged": ("decode_step", _stale_cache),
    }[name]
    saved = getattr(cont, attr)
    setattr(cont, attr, broken(saved))
    try:
        yield
    finally:
        setattr(cont, attr, saved)
