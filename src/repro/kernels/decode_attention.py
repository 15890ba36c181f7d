"""Decode (single-token) attention for TPU (Pallas).

The decode hot spot is memory-bound: one query row streams the whole KV
cache through VMEM.  TPU adaptation:
  * grid = (B, K_heads, S/block_k) with the cache-block dimension sequential;
    running (m, l, acc) in VMEM scratch — flash-decoding without the CUDA
    split-k reduction kernel (the sequential grid does the combine in-place);
  * all q heads of one KV group are processed together as a (group, D) tile —
    GQA turns the dot into a (group x D) @ (D x block_k) MXU matmul instead
    of `group` separate vector dots, recovering MXU utilization;
  * variable cache lengths handled by masking against `cache_len`, which
    rides in SMEM as a scalar-prefetch operand (a (B,) vector cannot be
    blocked per row in VMEM: its block would be (1,), not (8, 128)-tiled).

For sequence-sharded caches (tp > kv_heads), `ops.decode_attention` wraps
this with a partial-softmax (m, l, acc) tree-combine over the model axis.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    cache_len_ref,  # (B,) int32 scalar prefetch (SMEM), one per batch row
    q_ref,  # (group, D)
    k_ref,  # (block_k, D)
    v_ref,  # (block_k, D)
    o_ref,  # (group, D)
    m_scr,  # (group,)
    l_scr,  # (group,)
    acc_scr,  # (group, D)
    *,
    scale: float,
    logit_cap: Optional[float],
    window: Optional[int],
    block_k: int,
    num_k_blocks: int,
):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    clen = cache_len_ref[pl.program_id(0)]
    blk_start = kj * block_k
    # live block: overlaps [max(0, clen-window), clen)
    lo = jnp.maximum(0, clen - window) if (window is not None and window > 0) else 0
    live = jnp.logical_and(blk_start < clen, blk_start + block_k > lo)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (group, block_k)
        if logit_cap is not None and logit_cap > 0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        pos = blk_start + jax.lax.iota(jnp.int32, block_k)
        mask = pos < clen
        if window is not None and window > 0:
            mask &= pos > clen - 1 - window
        s = jnp.where(mask[None, :], s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask[None, :], jnp.exp(s - m_new[:, None]), 0.0)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p,
            v_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = m_new

    @pl.when(kj == num_k_blocks - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,  # (B, H, D)
    k_cache: jnp.ndarray,  # (B, S, K, D)
    v_cache: jnp.ndarray,  # (B, S, K, D)
    cache_len: jnp.ndarray,  # (B,) int32
    *,
    logit_cap: Optional[float] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_k: int = 256,
    interpret: Optional[bool] = None,  # None: interpret unless on a TPU
) -> jnp.ndarray:
    B, H, D = q.shape
    _, S, K, _ = k_cache.shape
    assert H % K == 0
    group = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    rem = S % block_k
    if rem:
        # Pad the cache out to a whole number of blocks.  The pad rows sit at
        # positions >= S >= cache_len, so the `pos < clen` mask already
        # excludes them — arbitrary max_len values work, no partial-block
        # kernel variant needed.
        pad = block_k - rem
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
        S += pad
    n_k = S // block_k

    qg = q.reshape(B, K, group, D)  # group q-heads by kv head
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, K, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    clen = cache_len.astype(jnp.int32).reshape(B)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(
        _decode_kernel,
        scale=scale,
        logit_cap=logit_cap,
        window=window,
        block_k=block_k,
        num_k_blocks=n_k,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, n_k),
            in_specs=[
                pl.BlockSpec((None, None, group, D), lambda b, h, j, cl: (b, h, 0, 0)),
                pl.BlockSpec((None, None, block_k, D), lambda b, h, j, cl: (b, h, j, 0)),
                pl.BlockSpec((None, None, block_k, D), lambda b, h, j, cl: (b, h, j, 0)),
            ],
            out_specs=pl.BlockSpec(
                (None, None, group, D), lambda b, h, j, cl: (b, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((group,), jnp.float32),
                pltpu.VMEM((group,), jnp.float32),
                pltpu.VMEM((group, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, group, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(clen, qg.reshape(B, K, group, D), kt, vt)
    return out.reshape(B, H, D)
