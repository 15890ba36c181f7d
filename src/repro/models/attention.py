"""GQA/MHA attention block: train (full), prefill (cache fill), decode
(single token), optional cross-attention (enc-dec).

KV-cache layout per layer: {"k": (B, Smax, K, hd), "v": (B, Smax, K, hd)},
stacked over the stage's layers; the layer gets its place in the stack as a
`cache_update.LayerCache`, writes its new rows through it and attends
against its own leaves.  `cache_len` is a scalar (aligned batched serving)
or a per-row (B,) vector (continuous batching: every slot decodes at its
own position).  Sharding: batch over dp.
For the cache's head dim: if K % tp == 0 heads shard over tp; otherwise the
*sequence* dim shards over tp and the decode softmax reductions become
all-reduces (flash-decoding across the model axis) — handled purely by
sharding constraints, see `cache_logical_spec`.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops

from . import layers
from .cache_update import LayerCache
from .layers import Params, apply_rope, dense_init, rmsnorm, rmsnorm_init
from .sharding import DP, TP, current_mesh, shard


def attn_init(
    key,
    cfg: ModelConfig,
    *,
    q_in_dim: Optional[int] = None,
    kv_in_dim: Optional[int] = None,
    dtype=jnp.float32,
) -> Params:
    D = cfg.d_model
    qd = q_in_dim or D
    kvd = kv_in_dim or D
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 8)
    p: Params = {
        "wq": dense_init(ks[0], qd, H, hd, dtype=dtype),
        "wk": dense_init(ks[1], kvd, K, hd, dtype=dtype),
        "wv": dense_init(ks[2], kvd, K, hd, dtype=dtype),
        "wo": dense_init(ks[3], H, hd, D, dtype=dtype),
    }
    if cfg.attn_bias:
        p["wq_b"] = jnp.zeros((H, hd), dtype)
        p["wk_b"] = jnp.zeros((K, hd), dtype)
        p["wv_b"] = jnp.zeros((K, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> Dict[str, jnp.ndarray]:
    K, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, K, hd), dtype),
        "v": jnp.zeros((batch, max_len, K, hd), dtype),
    }


def _dp_size() -> int:
    mesh = current_mesh()
    if mesh is None:
        return 1
    import numpy as _np

    return int(_np.prod([mesh.shape[a] for a in ("pod", "data") if a in mesh.axis_names]) or 1)


def cache_logical_spec(cfg: ModelConfig, tp_size: int, batch: int) -> Tuple:
    """(B, S, K, hd) logical axes for the KV cache.  Must agree with
    launch/shardings.py:cache_pspec.

    `tp_size` is the model axis' size, 0 where the mesh has none (or there
    is no mesh, as on one chip): with 0 or 1 there is nothing to split the
    heads over, so the heads take the tp slot (a no-op) and the sequence
    axis stays whole."""
    dp_n = _dp_size()
    heads_ok = tp_size <= 1 or cfg.n_kv_heads % tp_size == 0
    if batch % max(dp_n, 1) == 0 and batch >= dp_n:
        return (DP, None, TP, None) if heads_ok else (DP, TP, None, None)
    # tiny batch (long-context decode): shard the sequence dim
    return (None, DP, TP, None) if heads_ok else (None, (DP, TP), None, None)


def _project_qkv(p: Params, xq: jnp.ndarray, xkv: jnp.ndarray, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"])
    if cfg.attn_bias:
        q = q + p["wq_b"][None, None]
        k = k + p["wk_b"][None, None]
        v = v + p["wv_b"][None, None]
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], eps=cfg.rms_eps)
        k = rmsnorm(k, p["k_norm"], eps=cfg.rms_eps)
    return q, k, v


def _tp_size() -> int:
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return 0
    return mesh.shape["model"]


def attn_apply(
    p: Params,
    x: jnp.ndarray,  # (B, S, D)
    cfg: ModelConfig,
    *,
    window: Optional[int] = None,
    causal: bool = True,
    positions: Optional[jnp.ndarray] = None,  # (S,) or per-row (B, S)
    cache: Optional[LayerCache] = None,  # this layer's place in the stage's {k, v}
    cache_len: Optional[jnp.ndarray] = None,  # scalar or per-row (B,) int32
    cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # encoder k, v
    use_rope: bool = True,
) -> Tuple[jnp.ndarray, Optional[LayerCache]]:
    """Returns (out, cache): `cache` is the handle after this layer wrote
    its new rows, None without a cache."""
    B, S, D = x.shape
    tp = _tp_size()
    scale = cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(cfg.hd)

    if cross_kv is not None:
        # cross-attention: kv precomputed from encoder (no cache update here)
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.attn_bias:
            q = q + p["wq_b"][None, None]
        k, v = cross_kv
        out = ops.flash_attention(q, k, v, causal=False, scale=scale)
        out = shard(out, DP, None, TP, None)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), None

    q, k, v = _project_qkv(p, x, x, cfg)
    if use_rope and cfg.pos_embedding == "rope":
        if positions is None:
            positions = jnp.arange(S)
        pos_b = positions if positions.ndim == 2 else positions[None, :]
        q = apply_rope(q, pos_b, cfg.rope_theta)
        k = apply_rope(k, pos_b, cfg.rope_theta)
    q = shard(q, DP, None, TP, None)

    if cache is None:
        # train / no-cache prefill
        out = ops.flash_attention(
            q, k, v,
            causal=causal,
            window=window,
            logit_cap=cfg.attn_softcap,
            scale=scale,
        )
        out = shard(out, DP, None, TP, None)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), None

    spec = cache_logical_spec(cfg, tp, B)
    if S > 1:
        # lay fresh k/v out like the cache BEFORE the update — otherwise SPMD
        # falls back to replicate-then-repartition around the write
        k = shard(k, *spec)
        v = shard(v, *spec)
    cache = cache.write({"k": k, "v": v}, cache_len, spec=spec)
    if S == 1:
        # decode: attend against the layer's cache, the new row included
        kv = cache.read()
        out = ops.decode_attention(
            q[:, 0],
            shard(kv["k"], *spec),
            shard(kv["v"], *spec),
            jnp.broadcast_to(jnp.atleast_1d(cache_len) + 1, (B,)).astype(jnp.int32),
            logit_cap=cfg.attn_softcap,
            window=window,
            scale=scale,
        )[:, None]  # (B, 1, H, hd)
    else:
        # prefill: attend causally within the segment just written
        out = ops.flash_attention(
            q, k, v,
            causal=causal,
            window=window,
            logit_cap=cfg.attn_softcap,
            q_offset=0,
            scale=scale,
        )
    out = shard(out, DP, None, TP, None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache


def cross_kv_init(p: Params, enc_out: jnp.ndarray, cfg: ModelConfig):
    """Precompute encoder K/V for decoder cross-attention layers."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    if cfg.attn_bias:
        k = k + p["wk_b"][None, None]
        v = v + p["wv_b"][None, None]
    return k, v
