"""Transformer block assembly: per-family layer stacks, scanned.

Scan-over-layers keeps compile time and HLO size O(1) in depth (126-layer
llama3-405b compiles one layer body).  Heterogeneous depth patterns are
expressed as *periods*: params are stacked (L/period, period, ...) and the
scan body unrolls the period statically (gemma2: [local, global]; xlstm:
[7 x mLSTM, sLSTM]; zamba2: [6 x mamba + shared-attn]).

Each stage function has signature
    stage_apply(params, h, cfg, mode, cache, cache_len, ...)
      -> (h, new_cache, aux_losses)
where cache is the stage's stacked cache pytree (or None in train mode).
Attention K/V stacks ride in the scan's carry and each layer writes its new
rows into them in place through a `cache_update.LayerCache`; recurrent
states, replaced whole every step, are scanned inputs and outputs.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

from . import attention as attn
from . import mamba2 as mb
from . import mla as mla_mod
from . import moe as moe_mod
from . import xlstm as xl
from .cache_update import LayerCache
from .layers import Params, mlp_apply, mlp_init, rmsnorm, rmsnorm_init, scan_unroll
from .sharding import residual_shard, shard


def _stack_init(key, n: int, init_fn):
    """Initialize n copies of a param pytree, stacked on axis 0."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _remat(f, enabled: bool):
    if not enabled:
        return f
    import os

    pol = os.environ.get("REPRO_REMAT_POLICY", "nothing")
    if pol == "none":
        return f
    policy = {
        # full remat: save only layer inputs — the right default at scale
        "nothing": jax.checkpoint_policies.nothing_saveable,
        # save matmul outputs: cheaper recompute, ~4x the activation memory
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }[pol]
    return jax.checkpoint(f, policy=policy)


# ---------------------------------------------------------------------------
# dense / vlm / moe decoder layer
# ---------------------------------------------------------------------------

def decoder_layer_init(key, cfg: ModelConfig, *, use_moe: bool, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {"ln1": rmsnorm_init(cfg.d_model, dtype), "ln2": rmsnorm_init(cfg.d_model, dtype)}
    if cfg.sandwich_norm:
        p["ln1_post"] = rmsnorm_init(cfg.d_model, dtype)
        p["ln2_post"] = rmsnorm_init(cfg.d_model, dtype)
    if cfg.mla is not None:
        p["attn"] = mla_mod.mla_init(k1, cfg, dtype=dtype)
    else:
        p["attn"] = attn.attn_init(k1, cfg, dtype=dtype)
    if use_moe:
        p["moe"] = moe_mod.moe_init(k2, cfg, dtype=dtype)
    else:
        p["mlp"] = mlp_init(k3, cfg.d_model, cfg.d_ff, dtype=dtype)
    return p


def decoder_layer_apply(
    p: Params,
    h: jnp.ndarray,
    cfg: ModelConfig,
    *,
    window: Optional[int],
    positions: jnp.ndarray,
    cache: Optional[LayerCache],
    cache_len: Optional[jnp.ndarray],
    use_moe: bool,
) -> Tuple[jnp.ndarray, Optional[LayerCache], jnp.ndarray]:
    h = residual_shard(h)
    x = rmsnorm(h, p["ln1"], eps=cfg.rms_eps)
    if cfg.mla is not None:
        a_out, new_cache = mla_mod.mla_apply(
            p["attn"], x, cfg, positions=positions, cache=cache, cache_len=cache_len
        )
    else:
        a_out, new_cache = attn.attn_apply(
            p["attn"], x, cfg,
            window=window, positions=positions, cache=cache, cache_len=cache_len,
        )
    if cfg.sandwich_norm:
        a_out = rmsnorm(a_out, p["ln1_post"], eps=cfg.rms_eps)
    h = h + a_out

    x = rmsnorm(h, p["ln2"], eps=cfg.rms_eps)
    aux = jnp.zeros((), jnp.float32)
    if use_moe:
        m_out, aux = moe_mod.moe_apply(p["moe"], x, cfg)
    else:
        m_out = mlp_apply(p["mlp"], x, cfg.act)
    if cfg.sandwich_norm:
        m_out = rmsnorm(m_out, p["ln2_post"], eps=cfg.rms_eps)
    return h + m_out, new_cache, aux


# ---------------------------------------------------------------------------
# decoder stage (scan over layers, period-aware)
# ---------------------------------------------------------------------------

def decoder_stage_init(
    key, cfg: ModelConfig, n_layers: int, *, use_moe: bool, dtype=jnp.float32
) -> Params:
    period = cfg.global_every if (cfg.sliding_window and cfg.global_every) else 1
    assert n_layers % period == 0, (n_layers, period)
    outer = n_layers // period

    def one(k):
        ks = jax.random.split(k, period)
        sub = [decoder_layer_init(ks[i], cfg, use_moe=use_moe, dtype=dtype) for i in range(period)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *sub)

    return _stack_init(key, outer, one)  # (outer, period, ...)


def decoder_stage_apply(
    params: Params,
    h: jnp.ndarray,
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,
    cache: Optional[Dict] = None,
    cache_len: Optional[jnp.ndarray] = None,
    use_moe: bool,
    remat: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Scan the stage's layers, (outer, period) stacked, period unrolled.

    The stage owns the cache: the stacked (outer, period, B, S, ...) leaves
    ride in the scan's carry, and layer [l, i] gets a
    `cache_update.LayerCache` on them.  The layer hands it the rows it
    produced (one per slot in decode, the segment in prefill), which are
    written at [l, i, b, cache_len[b]] (or at the scalar `cache_len`) and
    nowhere else; the stage takes the updated stack back.  Returns
    (h, stack, aux) — stack None without a cache (training)."""
    period = cfg.global_every if (cfg.sliding_window and cfg.global_every) else 1

    def body(carry, xs):
        hh, aux, stack = carry
        layer_params, l = xs
        for i in range(period):
            pi = jax.tree_util.tree_map(lambda a, i=i: a[i], layer_params)
            ci = None if stack is None else LayerCache(stack, (l, i))
            window = None
            if cfg.sliding_window and period > 1 and i < period - 1:
                window = cfg.sliding_window
            elif cfg.sliding_window and period == 1:
                window = cfg.sliding_window
            hh, ci, a = decoder_layer_apply(
                pi, hh, cfg,
                window=window, positions=positions,
                cache=ci, cache_len=cache_len, use_moe=use_moe,
            )
            aux = aux + a
            stack = None if ci is None else ci.stack
        return (hh, aux, stack), None

    body = _remat(body, remat)
    (h, aux, new_cache), _ = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32), cache), (params, _layer_index(cache, params)),
        unroll=scan_unroll(),
    )
    return h, new_cache, aux


def _layer_index(cache: Optional[Dict], params: Params) -> Optional[jnp.ndarray]:
    """Scan input giving each iteration its index on the stacked cache's
    first axis; None without a cache, leaving the training scan as it is."""
    if cache is None:
        return None
    return jnp.arange(jax.tree_util.tree_leaves(params)[0].shape[0], dtype=jnp.int32)


# ---------------------------------------------------------------------------
# encoder stage (whisper): full attention, no cache
# ---------------------------------------------------------------------------

def encoder_layer_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn.attn_init(k1, cfg, dtype=dtype),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype),
    }


def encoder_stage_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    return _stack_init(
        key, cfg.n_encoder_layers, lambda k: encoder_layer_init(k, cfg, dtype=dtype)
    )


def encoder_stage_apply(params: Params, h: jnp.ndarray, cfg: ModelConfig, *, remat=False):
    def body(hh, layer):
        x = rmsnorm(hh, layer["ln1"], eps=cfg.rms_eps)
        a, _ = attn.attn_apply(layer["attn"], x, cfg, causal=False, use_rope=False)
        hh = hh + a
        x = rmsnorm(hh, layer["ln2"], eps=cfg.rms_eps)
        return hh + mlp_apply(layer["mlp"], x, cfg.act), None

    body = _remat(body, remat)
    h, _ = jax.lax.scan(body, h, params, unroll=scan_unroll())
    return h


# ---------------------------------------------------------------------------
# cross-decoder stage (whisper decoder: self + cross + mlp)
# ---------------------------------------------------------------------------

def xdecoder_layer_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "ln_x": rmsnorm_init(cfg.d_model, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "self_attn": attn.attn_init(k1, cfg, dtype=dtype),
        "cross_attn": attn.attn_init(k2, cfg, dtype=dtype),
        "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, dtype=dtype),
    }


def xdecoder_stage_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    return _stack_init(key, cfg.n_layers, lambda k: xdecoder_layer_init(k, cfg, dtype=dtype))


def xdecoder_stage_apply(
    params: Params,
    h: jnp.ndarray,
    cfg: ModelConfig,
    *,
    enc_out: Optional[jnp.ndarray] = None,  # (B, Senc, D) or None if cached
    positions: jnp.ndarray,
    cache: Optional[Dict] = None,
    cache_len: Optional[jnp.ndarray] = None,
    remat: bool = False,
):
    """cache: {"self": {k,v}, "cross": {k,v}} stacked (L, ...).

    The self-attention stack rides in the carry and is written in place;
    the cross K/V, computed once from `enc_out` (prefill), is read-only
    after that and passes through untouched."""
    cross = None if cache is None else cache.get("cross")

    def body(carry, xs):
        hh, stack = carry
        layer, layer_cross, l = xs
        x = rmsnorm(hh, layer["ln1"], eps=cfg.rms_eps)
        a, lc = attn.attn_apply(
            layer["self_attn"], x, cfg,
            positions=positions, cache=None if stack is None else LayerCache(stack, (l,)),
            cache_len=cache_len, use_rope=False,
        )
        hh = hh + a
        x = rmsnorm(hh, layer["ln_x"], eps=cfg.rms_eps)
        if layer_cross is not None:
            ck, cv = layer_cross["k"], layer_cross["v"]
        else:
            ck, cv = attn.cross_kv_init(layer["cross_attn"], enc_out, cfg)
        a, _ = attn.attn_apply(layer["cross_attn"], x, cfg, cross_kv=(ck, cv))
        hh = hh + a
        x = rmsnorm(hh, layer["ln2"], eps=cfg.rms_eps)
        hh = hh + mlp_apply(layer["mlp"], x, cfg.act)
        new_cross = None if stack is None or layer_cross is not None else {"k": ck, "v": cv}
        return (hh, None if lc is None else lc.stack), new_cross

    body = _remat(body, remat)
    self_cache = None if cache is None else cache["self"]
    (h, new_self), new_cross = jax.lax.scan(
        body, (h, self_cache), (params, cross, _layer_index(cache, params)),
        unroll=scan_unroll(),
    )
    new_cache = None
    if cache is not None:
        new_cache = {"self": new_self, "cross": new_cross if cross is None else cross}
    return h, new_cache


# ---------------------------------------------------------------------------
# hybrid stage (zamba2): mamba superblocks + shared attention block
# ---------------------------------------------------------------------------

def shared_attn_block_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    k1, k2 = jax.random.split(key)
    D = cfg.d_model
    return {
        "ln": rmsnorm_init(2 * D, dtype),
        "attn": attn.attn_init(k1, cfg, q_in_dim=2 * D, kv_in_dim=2 * D, dtype=dtype),
        "ln2": rmsnorm_init(2 * D, dtype),
        "mlp": {
            "w_gate": jax.random.normal(k2, (2 * D, cfg.d_ff)).astype(dtype) * (2 * D) ** -0.5,
            "w_up": jax.random.normal(jax.random.fold_in(k2, 1), (2 * D, cfg.d_ff)).astype(dtype)
            * (2 * D) ** -0.5,
            "w_down": jax.random.normal(jax.random.fold_in(k2, 2), (cfg.d_ff, D)).astype(dtype)
            * cfg.d_ff**-0.5,
        },
    }


def shared_attn_block_apply(
    p: Params,
    h: jnp.ndarray,
    h0: jnp.ndarray,  # original embeddings (zamba concat trick)
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,
    cache: Optional[LayerCache] = None,
    cache_len: Optional[jnp.ndarray] = None,
):
    xcat = jnp.concatenate([h, h0], axis=-1)  # (B, S, 2D)
    x = rmsnorm(xcat, p["ln"], eps=cfg.rms_eps)
    a, new_cache = attn.attn_apply(
        p["attn"], x, cfg, positions=positions, cache=cache, cache_len=cache_len
    )
    h = h + a
    x2 = rmsnorm(xcat, p["ln2"], eps=cfg.rms_eps)
    h = h + mlp_apply(p["mlp"], x2, cfg.act)
    return h, new_cache


def hybrid_stage_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    per = cfg.shared_attn_every
    n_super = cfg.n_layers // per
    n_tail = cfg.n_layers - n_super * per
    k1, k2, k3 = jax.random.split(key, 3)

    def superblock(k):
        ks = jax.random.split(k, per)
        subs = [mb.mamba2_init(ks[i], cfg, dtype=dtype) for i in range(per)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *subs)

    p: Params = {
        "super": _stack_init(k1, n_super, superblock),  # (n_super, per, ...)
        "shared": shared_attn_block_init(k2, cfg, dtype=dtype),
    }
    if n_tail:
        p["tail"] = _stack_init(k3, n_tail, lambda k: mb.mamba2_init(k, cfg, dtype=dtype))
    return p


def hybrid_stage_apply(
    params: Params,
    h: jnp.ndarray,
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,
    cache: Optional[Dict] = None,
    cache_len: Optional[jnp.ndarray] = None,
    remat: bool = False,
):
    per = cfg.shared_attn_every
    h0 = h  # embeddings for the concat trick

    def body(carry, xs):
        hh, attn_stack = carry
        layer_params, layer_states, l = xs
        mstates = []
        for i in range(per):
            pi = jax.tree_util.tree_map(lambda a, i=i: a[i], layer_params["mamba"])
            si = (
                None
                if layer_states is None
                else jax.tree_util.tree_map(lambda a, i=i: a[i], layer_states)
            )
            out, ns = mb.mamba2_apply(pi, hh, cfg, state=si)
            hh = hh + out
            mstates.append(ns)
        hh, lc = shared_attn_block_apply(
            params["shared"], hh, h0, cfg,
            positions=positions,
            cache=None if attn_stack is None else LayerCache(attn_stack, (l,)),
            cache_len=cache_len,
        )
        new_states = None
        if layer_states is not None:
            new_states = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *mstates)
        return (hh, None if lc is None else lc.stack), new_states

    body = _remat(body, remat)
    super_cache = None if cache is None else cache["super"]
    (h, new_attn), new_mamba = jax.lax.scan(
        body,
        (h, None if super_cache is None else super_cache["attn"]),
        (
            {"mamba": params["super"]},
            None if super_cache is None else super_cache["mamba"],
            _layer_index(cache, params["super"]),
        ),
        unroll=scan_unroll(),
    )

    new_tail = None
    if "tail" in params:
        def tail_body(carry, xs):
            hh = carry
            pi, si = xs
            out, ns = mb.mamba2_apply(pi, hh, cfg, state=si)
            return hh + out, ns

        tail_body = _remat(tail_body, remat)
        tail_cache = None if cache is None else cache["tail"]
        h, new_tail = jax.lax.scan(
            tail_body, h, (params["tail"], tail_cache), unroll=scan_unroll()
        )

    new_cache = None
    if cache is not None:
        new_cache = {"super": {"mamba": new_mamba, "attn": new_attn}, "tail": new_tail}
    return h, new_cache


# ---------------------------------------------------------------------------
# xlstm stage: groups of (slstm_every-1) mLSTM + 1 sLSTM
# ---------------------------------------------------------------------------

def xlstm_stage_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    per = cfg.xlstm.slstm_every
    n_groups = cfg.n_layers // per
    assert cfg.n_layers % per == 0
    k1, k2 = jax.random.split(key)

    def group_m(k):
        ks = jax.random.split(k, per - 1)
        subs = [xl.mlstm_block_init(ks[i], cfg, dtype=dtype) for i in range(per - 1)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *subs)

    return {
        "mlstm": _stack_init(k1, n_groups, group_m),  # (G, per-1, ...)
        "slstm": _stack_init(k2, n_groups, lambda k: xl.slstm_block_init(k, cfg, dtype=dtype)),
    }


def xlstm_stage_apply(
    params: Params,
    h: jnp.ndarray,
    cfg: ModelConfig,
    *,
    cache: Optional[Dict] = None,
    remat: bool = False,
):
    per = cfg.xlstm.slstm_every

    def body(carry, xs):
        hh = carry
        p_m, p_s, c_m, c_s = xs["m"], xs["s"], xs["cm"], xs["cs"]
        new_m = []
        for i in range(per - 1):
            pi = jax.tree_util.tree_map(lambda a, i=i: a[i], p_m)
            si = None if c_m is None else jax.tree_util.tree_map(lambda a, i=i: a[i], c_m)
            hh, ns = xl.mlstm_block_apply(pi, hh, cfg, state=si)
            new_m.append(ns)
        hh, new_s = xl.slstm_block_apply(p_s, hh, cfg, state=c_s)
        nm = (
            None
            if new_m[0] is None
            else jax.tree_util.tree_map(lambda *xs_: jnp.stack(xs_), *new_m)
        )
        return hh, {"m": nm, "s": new_s}

    body = _remat(body, remat)
    xs = {
        "m": params["mlstm"],
        "s": params["slstm"],
        "cm": None if cache is None else cache["m"],
        "cs": None if cache is None else cache["s"],
    }
    h, new_cache = jax.lax.scan(body, h, xs, unroll=scan_unroll())
    return h, (new_cache if cache is not None else None)
