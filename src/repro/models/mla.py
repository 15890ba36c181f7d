"""Multi-head Latent Attention (DeepSeek-V2/V3).

KV state is compressed to a per-token latent c_kv (rank 512) plus a shared
decoupled-RoPE key k_pe (64), cutting KV-cache bytes ~14x vs GQA at 128
heads.  Two execution forms:

  * train/prefill: up-project latent to per-head K (nope‖rope, 192) and
    V (128), run flash attention (Dv != Dqk handled by the jnp path);
  * decode: *weight absorption* — fold W_UK into the query so scores are
    taken directly against the latent cache: q_lat = q_nope · W_UK, then
    scores = q_lat·c_kv + q_rope·k_pe; context is accumulated in latent
    space and up-projected once with W_UV.  FLOPs per token drop from
    O(S·H·192) to O(S·(512+64)) on the score side.

Cache sharding: (B, S, r) latent is head-free, so the sequence dim shards
over the model axis (the decode softmax reductions become all-reduces —
flash-decoding via SPMD).  The stage owns the stacked latent cache; the
layer writes its rows through its `cache_update.LayerCache`, which picks
the masked write only where that model axis really splits the sequence.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops

from .cache_update import LayerCache
from .layers import Params, apply_rope, dense_init, rmsnorm, rmsnorm_init
from .sharding import DP, TP, shard


def mla_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk_head = m.nope_head_dim + m.rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "q_down": dense_init(ks[0], D, m.q_lora_rank, dtype=dtype),
        "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
        "q_up": dense_init(ks[1], m.q_lora_rank, H, qk_head, dtype=dtype),
        "kv_down": dense_init(ks[2], D, m.kv_lora_rank + m.rope_head_dim, dtype=dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "kv_up": dense_init(
            ks[3], m.kv_lora_rank, H, m.nope_head_dim + m.v_head_dim, dtype=dtype
        ),
        "wo": dense_init(ks[4], H, m.v_head_dim, D, dtype=dtype),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_pe": jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
    }


def mla_cache_spec() -> Tuple:
    return (DP, TP, None)  # sequence-sharded latent


def _q_heads(p: Params, x: jnp.ndarray, cfg: ModelConfig, positions: jnp.ndarray):
    m = cfg.mla
    q_lat = rmsnorm(x @ p["q_down"], p["q_norm"], eps=cfg.rms_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["q_up"])
    q_nope = q[..., : m.nope_head_dim]
    pos_b = positions if positions.ndim == 2 else positions[None, :]
    q_pe = apply_rope(q[..., m.nope_head_dim :], pos_b, cfg.rope_theta)
    return q_nope, q_pe


def _latent(p: Params, x: jnp.ndarray, cfg: ModelConfig, positions: jnp.ndarray):
    m = cfg.mla
    kv = x @ p["kv_down"]  # (B, S, r + rope)
    c_kv = rmsnorm(kv[..., : m.kv_lora_rank], p["kv_norm"], eps=cfg.rms_eps)
    pos_b = positions if positions.ndim == 2 else positions[None, :]
    k_pe = apply_rope(
        kv[..., m.kv_lora_rank :][:, :, None, :], pos_b, cfg.rope_theta
    )[:, :, 0]  # (B, S, rope)
    return c_kv, k_pe


def mla_apply(
    p: Params,
    x: jnp.ndarray,  # (B, S, D)
    cfg: ModelConfig,
    *,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[LayerCache] = None,  # this layer's place in the stage's {c_kv, k_pe}
    cache_len: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[LayerCache]]:
    """Returns (out, cache): `cache` is the handle after this layer wrote
    its latent rows, None without a cache."""
    B, S, D = x.shape
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)
    if positions is None:
        positions = jnp.arange(S)

    q_nope, q_pe = _q_heads(p, x, cfg, positions)
    c_kv, k_pe = _latent(p, x, cfg, positions)

    if cache is not None and S == 1:
        # ---- absorbed decode ------------------------------------------
        cache = cache.write({"c_kv": c_kv, "k_pe": k_pe}, cache_len, spec=mla_cache_spec())
        lat = cache.read()
        new_ckv = shard(lat["c_kv"], *mla_cache_spec())
        new_kpe = shard(lat["k_pe"], *mla_cache_spec())

        kv_up_k = p["kv_up"][..., : m.nope_head_dim]  # (r, H, nope)
        kv_up_v = p["kv_up"][..., m.nope_head_dim :]  # (r, H, v)
        q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], kv_up_k)  # (B,H,r)
        q_lat = shard(q_lat, DP, TP, None)

        s_lat = jnp.einsum("bhr,bsr->bhs", q_lat, new_ckv.astype(jnp.float32))
        s_pe = jnp.einsum("bhk,bsk->bhs", q_pe[:, 0], new_kpe.astype(jnp.float32))
        scores = (s_lat + s_pe) * scale  # (B, H, S)
        pos = jnp.arange(new_ckv.shape[1])[None, None, :]
        clen = cache_len
        if jnp.ndim(clen) == 1:
            clen = clen[:, None, None]  # per-slot lengths (continuous batching)
        scores = jnp.where(pos <= clen, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx_lat = jnp.einsum("bhs,bsr->bhr", probs, new_ckv.astype(jnp.float32))
        ctx = jnp.einsum("bhr,rhv->bhv", ctx_lat, kv_up_v.astype(jnp.float32))
        out = jnp.einsum("bhv,hvd->bd", ctx.astype(x.dtype), p["wo"])[:, None]
        return out, cache

    # ---- train / prefill: materialize per-head K and V ------------------
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["kv_up"])
    k_nope = kv[..., : m.nope_head_dim]
    v = kv[..., m.nope_head_dim :]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (*k_nope.shape[:3], m.rope_head_dim))],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    q = shard(q, DP, None, TP, None)
    k = shard(k, DP, None, TP, None)
    v = shard(v, DP, None, TP, None)
    out = ops.flash_attention(q, k, v, causal=True, scale=scale)
    out = shard(out, DP, None, TP, None)
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])

    if cache is not None:
        cache = cache.write({"c_kv": c_kv, "k_pe": k_pe}, cache_len, spec=mla_cache_spec())
    return y, cache
