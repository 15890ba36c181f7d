"""Dependency-free utilities shared across layers."""

import os
from pathlib import Path

# the repository root: src/repro/util.py -> ../../
REPO_ROOT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> None:
    """Point JAX's persistent compilation cache at a fixed directory.

    Entry points call this before their first compile.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is changed.  Otherwise the cache lives in ``.jax_cache/`` at the
    repository root: a fixed path, so the next run of any entry point from
    this checkout finds what the last one compiled."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_ROOT / ".jax_cache"))


def scan_unroll():
    """Scan unroll factor for layer/block scans.

    Default 1 (rolled: fast compiles, tiny HLO).  The dry-run sets
    REPRO_SCAN_UNROLL=full so `compiled.cost_analysis()` counts every layer
    (XLA costs a while-loop body ONCE regardless of trip count — rolled
    compiles undercount FLOPs/collective bytes by ~n_layers)."""
    v = os.environ.get("REPRO_SCAN_UNROLL", "1")
    return True if v == "full" else max(int(v), 1)


def inner_unroll():
    """Unroll factor for kernel-level inner scans (attention KV blocks, SSD
    chunks, mLSTM blocks).  Kept separate from layer-scan unroll: inner scans
    contain no collectives, so the dry-run can keep them rolled in compiled
    probes (small graphs, fast CPU codegen) while counting their FLOPs from
    fully-unrolled *lowered* modules."""
    v = os.environ.get("REPRO_INNER_UNROLL", "1")
    return True if v == "full" else max(int(v), 1)
