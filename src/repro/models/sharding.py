"""Mesh-aware logical sharding helpers.

Model code annotates activations with *logical* axes ("dp", "tp", None);
this module maps them onto whatever physical mesh is ambient:
  * production single-pod: (data=16, model=16)        dp=(data,) tp=model
  * production multi-pod:  (pod=2, data=16, model=16) dp=(pod,data) tp=model
  * CPU smoke tests: no mesh -> all constraints are no-ops.

Parameter shardings are assigned by path-pattern rules (`param_pspec`),
giving Megatron-style TP over "model" + ZeRO-3/FSDP over the combined
data axes.
"""

from __future__ import annotations

import math
import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP = "dp"  # data-parallel / FSDP logical axis -> ("pod","data") subset
TP = "tp"  # tensor/expert-parallel logical axis -> "model"


def current_mesh() -> Optional[Mesh]:
    """The mesh entered with `jax.set_mesh`, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def axis_map() -> str:
    """Logical->physical mapping scheme (a §Perf hillclimb lever):
      tp_model (default): dp -> (pod, data), tp -> model   (FSDP+TP16)
      fsdp_all:           dp -> (pod, data, model), tp -> —  (pure ZeRO-3;
                          kills TP activation all-reduces; right for models
                          whose layer params fit HBM when gathered)
    """
    import os

    return os.environ.get("REPRO_AXIS_MAP", "tp_model")


def seq_parallel() -> bool:
    """Megatron-style sequence parallelism for the residual stream: hidden
    states (B, S, D) are sharded over tp on S between blocks, shrinking the
    per-layer saved activations tp-fold (a §Perf hillclimb lever)."""
    import os

    return os.environ.get("REPRO_SEQ_PARALLEL", "0") == "1"


def physical_axes(mesh: Mesh, logical):
    if logical is None:
        return None
    if isinstance(logical, tuple):  # combined logical axes, e.g. ("dp","tp")
        out = []
        for l in logical:
            ax = physical_axes(mesh, l)
            if ax is None:
                continue
            out.extend(ax if isinstance(ax, tuple) else (ax,))
        return tuple(out) if out else None
    names = set(mesh.axis_names)
    scheme = axis_map()
    if logical == DP:
        pool = ("pod", "data", "model") if scheme == "fsdp_all" else ("pod", "data")
        axes = tuple(a for a in pool if a in names)
        return axes if axes else None
    if logical == TP:
        if scheme == "fsdp_all":
            return None
        return "model" if "model" in names else None
    # literal mesh axis name passthrough
    return logical if logical in names else None


def devices_along(logical) -> int:
    """How many devices the ambient mesh splits a dimension over when its
    logical spec is `logical` (1 without a mesh, or where the axes it maps
    to all have size 1)."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    ax = physical_axes(mesh, logical)
    if ax is None:
        return 1
    return math.prod(mesh.shape[a] for a in (ax if isinstance(ax, tuple) else (ax,)))


def make_pspec(mesh: Mesh, *logical) -> P:
    return P(*(physical_axes(mesh, l) for l in logical))


def shard(x: jnp.ndarray, *logical) -> jnp.ndarray:
    """with_sharding_constraint against the ambient mesh (no-op without)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(logical) != x.ndim:
        raise ValueError(f"spec {logical} does not match rank-{x.ndim} array")
    return jax.lax.with_sharding_constraint(x, make_pspec(mesh, *logical))


def residual_shard(x: jnp.ndarray) -> jnp.ndarray:
    """Constraint for the (B, S, D) residual stream between blocks: batch
    over dp, and — under sequence parallelism — S over tp."""
    mesh = current_mesh()
    if mesh is None or x.ndim != 3:
        return x
    tp_ax = physical_axes(mesh, TP)
    if seq_parallel() and tp_ax is not None:
        tp_size = mesh.shape[tp_ax]
        if x.shape[1] % tp_size == 0 and x.shape[1] >= tp_size:
            return shard(x, DP, TP, None)
    return shard(x, DP, None, None)


# ---------------------------------------------------------------------------
# parameter sharding rules (path-pattern based)
# ---------------------------------------------------------------------------
# Each rule: (regex over 'a/b/c' param path, logical spec builder given ndim).
# Conventions (dims AFTER the scan-stacking axes, which are always None):
#   embeddings (V, D)           -> (tp, dp)    vocab-sharded
#   attn wq (D, H, hd)          -> (dp, tp, None)
#   attn wk/wv (D, K, hd)       -> (dp, tp, None)  (replicate tp if K < tp)
#   attn wo (H, hd, D)          -> (tp, None, dp)
#   mlp w_gate/w_up (D, F)      -> (dp, tp)
#   mlp w_down (F, D)           -> (tp, dp)
#   moe experts (E, D, F)       -> (tp, dp, None)   expert-parallel
#   moe w_down (E, F, D)        -> (tp, None, dp)
#   router (D, E)               -> (dp, None)
#   mamba in/out proj           -> (dp, tp) / (tp, dp)
#   norms / scalars / biases    -> replicated
# FSDP ("dp") on the non-tp dim gives ZeRO-3: XLA all-gathers per layer.

_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"embed/tok$", (TP, DP)),
    (r"embed/pos$", (None, None)),
    (r"lm_head$", (DP, TP)),
    (r"(wq|q_up)$", (DP, TP, None)),
    (r"(wk|wv)$", (DP, None, None)),
    (r"wo$", (TP, None, DP)),
    (r"(wq_b|wk_b|wv_b)$", (None, None)),
    (r"q_down$", (DP, TP)),
    (r"kv_down$", (DP, None)),
    (r"kv_up$", (DP, TP, None)),
    (r"(w_gate|w_up)$", (DP, TP)),
    (r"w_down$", (TP, DP)),
    (r"experts/(w_gate|w_up)$", (TP, DP, None)),
    (r"experts/w_down$", (TP, None, DP)),
    (r"router$", (DP, None)),
    (r"in_proj$", (DP, TP)),
    (r"out_proj$", (TP, DP)),
    (r"(conv_kernel|conv_bias)$", (None, TP)),
    (r"(A_log|D|dt_bias)$", (TP,)),
    (r"(w_q|w_k|w_v)hw$", (TP, None, None)),  # headwise xlstm projections
    (r"(w_i|w_f)gate$", (DP, TP)),
    (r"r_kernel$", (TP, None, None, None)),
    (r"gates_x$", (DP, TP, None)),
    (r"skip$", (TP,)),
)


def _match_logical(path: str, shape: Tuple[int, ...]) -> Tuple[Optional[str], ...]:
    for pat, spec in _RULES:
        if re.search(pat, path):
            nlead = len(shape) - len(spec)
            if nlead < 0:
                return tuple([None] * len(shape))
            return tuple([None] * nlead + list(spec))
    return tuple([None] * len(shape))  # replicate


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspec(mesh: Mesh, params_tree: Any, *, verify_divisible: bool = True) -> Any:
    """PartitionSpec pytree for a param pytree (shapes or arrays)."""

    def spec_for(path, leaf):
        shape = leaf.shape
        logical = _match_logical(_path_str(path), shape)
        phys = []
        for dim, l in zip(shape, logical):
            ax = physical_axes(mesh, l)
            if ax is None:
                phys.append(None)
                continue
            size = (
                mesh.shape[ax]
                if isinstance(ax, str)
                else int(jnp.prod(jnp.array([mesh.shape[a] for a in ax])))
            )
            if verify_divisible and dim % size != 0:
                phys.append(None)  # fall back to replication
            else:
                phys.append(ax)
        return P(*phys)

    return jax.tree_util.tree_map_with_path(spec_for, params_tree)


def param_sharding(mesh: Mesh, params_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_pspec(mesh, params_tree)
    )
