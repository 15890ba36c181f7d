"""Decode (single-token) attention for TPU (Pallas).

The decode hot spot is memory-bound: one query row per slot streams that
slot's live KV cache through VMEM.  TPU adaptation:
  * the cache is read in its stored (B, S, K, D) layout: one block is
    (block_k, K, D), all KV heads of one slot, so no transpose of the cache
    is materialised; inside a grid step each head h reads `k_ref[:, h, :]`;
  * grid = (B, S/block_k) with the cache-block dimension sequential;
    running (m, l, acc) for all H query heads in VMEM scratch —
    flash-decoding without the CUDA split-k reduction kernel (the
    sequential grid does the combine in-place);
  * all q heads of one KV group are processed together as a (group, D) tile —
    GQA turns the dot into a (group x D) @ (D x block_k) MXU matmul instead
    of `group` separate vector dots, recovering MXU utilization;
  * variable cache lengths: `cache_len` rides in SMEM as a scalar-prefetch
    operand (a (B,) vector cannot be blocked per row in VMEM: its block
    would be (1,), not (8, 128)-tiled).  The K/V index maps clamp the
    block index into the slot's live blocks (`live_block`), so a dead grid
    step repeats its neighbour's block and the pipeline fetches nothing
    for it; `pl.when` skips its compute, and positions past `cache_len`
    (or before the window) are masked inside a live block.

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

# VMEM the K and V blocks may take, double-buffered: well inside the 16 MiB
# a v5e kernel may use by default, with room for q, out and the scratch.
KV_VMEM_BUDGET = 8 * 1024 * 1024


def live_blocks(clen, window: Optional[int], block_k: int):
    """First and last cache block of a slot of length `clen`: the blocks
    overlapping its live positions [max(0, clen - window), clen).  A free
    slot (`clen` 0) gets block 0 alone, where every position is masked."""
    first = 0
    if window is not None and window > 0:
        first = jnp.maximum(clen - window, 0) // block_k
    return first, jnp.maximum(clen - 1, 0) // block_k


def live_block(j, clen, window: Optional[int], block_k: int):
    """The cache block grid step `j` reads: `j` clamped into the slot's
    live blocks.  A dead step maps onto the nearest live block, the one its
    neighbouring step reads, so the pipeline issues no DMA for it."""
    return jnp.clip(j, *live_blocks(clen, window, block_k))


def _decode_kernel(
    cache_len_ref,  # (B,) int32 scalar prefetch (SMEM), one per batch row
    q_ref,  # (H, D)
    k_ref,  # (block_k, K, D)
    v_ref,  # (block_k, K, D)
    o_ref,  # (H, D)
    m_scr,  # (H, 1)
    l_scr,  # (H, 1)
    acc_scr,  # (H, D)
    *,
    scale: float,
    logit_cap: Optional[float],
    window: Optional[int],
    block_k: int,
    num_k_blocks: int,
):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    clen = cache_len_ref[pl.program_id(0)]
    blk_start = kj * block_k
    first, last = live_blocks(clen, window, block_k)
    live = jnp.logical_and(kj >= first, kj <= last)

    @pl.when(live)
    def _compute():
        kv_heads = k_ref.shape[1]
        group = q_ref.shape[0] // kv_heads
        q = q_ref[...].astype(jnp.float32) * scale
        # scores of every query head, each group against its own KV head
        s = jnp.concatenate(
            [
                jax.lax.dot_general(
                    q[h * group : (h + 1) * group],
                    k_ref[:, h, :].astype(jnp.float32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for h in range(kv_heads)
            ],
            axis=0,
        )  # (H, block_k)
        if logit_cap is not None and logit_cap > 0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        pos = blk_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = pos < clen
        if window is not None and window > 0:
            mask &= pos > clen - 1 - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.concatenate(
            [
                jax.lax.dot_general(
                    p[h * group : (h + 1) * group],
                    v_ref[:, h, :].astype(jnp.float32),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for h in range(kv_heads)
            ],
            axis=0,
        )  # (H, D)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(kj == num_k_blocks - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


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
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    # K and V, double-buffered at (block_k, K, D); the widest cache in the
    # repo (K 16, D 128, bf16) takes half the budget at 256
    assert 4 * block_k * K * D * k_cache.dtype.itemsize <= KV_VMEM_BUDGET
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

    def kv_index(b, j, cl):
        return (b, live_block(j, cl[b], window, block_k), 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_k),
            in_specs=[
                pl.BlockSpec((None, H, D), lambda b, j, cl: (b, 0, 0)),
                pl.BlockSpec((None, block_k, K, D), kv_index),
                pl.BlockSpec((None, block_k, K, D), kv_index),
            ],
            out_specs=pl.BlockSpec((None, H, D), lambda b, j, cl: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(clen, q, k_cache, v_cache)
