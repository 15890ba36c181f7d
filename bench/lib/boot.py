"""Start-up shared by the benchmark's entry points (`run.py`, `sweep.py`,
`calibrate.py`): the program on the path, JAX's compilation cache in this
checkout, and the chips the cell asks for."""

from __future__ import annotations

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    pass


def chips(n: int = 1):
    """The first TPU device, once JAX is set up to cache every program in
    `.jax_cache/` of this checkout (a fixed path, so that later runs here
    load what the first compiled).  Raises `NoChip` without a TPU or with
    fewer than `n` chips: the benchmark never falls back to the CPU."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChip(f"need {n} TPU chip(s), JAX sees {devices}")
    return devices[0]
