"""Flash attention for TPU (Pallas): online-softmax blockwise attention.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * blocks are (block_q x head_dim) @ (head_dim x block_k) MXU matmuls with
    both block dims multiples of 128 (MXU systolic shape) by default;
  * the KV loop is the innermost *sequential* grid dimension; running
    (m, l, acc) state lives in VMEM scratch that persists across grid steps —
    the TPU idiom replacing CUDA's per-CTA shared-memory accumulators;
  * GQA is folded into the BlockSpec index_map (q-head h reads kv-head
    h // group) so KV heads are never materialized repeated in HBM;
  * causal + sliding-window masks are computed from program ids; fully-masked
    KV blocks are skipped via `pl.when` (no MXU work), which matters for the
    window=4096 local layers of gemma2 where >87% of blocks are masked at 32k.

Supports: causal or full, sliding window, logit softcap (gemma2), q_offset
(decode/prefill continuation), any sequence lengths (padded to the block,
padded keys masked), value head dim != query head dim (MLA).  fp32
accumulation throughout.
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


def _flash_kernel(
    # refs
    q_ref,  # (block_q, D)
    k_ref,  # (block_k, D)
    v_ref,  # (block_k, Dv)
    o_ref,  # (block_q, Dv)
    # scratch
    m_scr,  # (block_q,) running max
    l_scr,  # (block_q,) running denom
    acc_scr,  # (block_q, Dv) running numerator
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    logit_cap: Optional[float],
    q_offset: int,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    kv_len: Optional[int],  # true key count when keys were padded, else None
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q) + q_offset  # (bq,)
    k_pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)  # (bk,)

    # block-level skip: is any (q, k) pair in this tile unmasked?
    q_lo, q_hi = qi * block_q + q_offset, qi * block_q + q_offset + block_q - 1
    k_lo, k_hi = kj * block_k, kj * block_k + block_k - 1
    live = True
    if kv_len is not None:
        live = jnp.logical_and(live, k_lo < kv_len)
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window is not None and window > 0:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if logit_cap is not None and logit_cap > 0:
            s = logit_cap * jnp.tanh(s / logit_cap)

        mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
        if kv_len is not None:
            mask &= k_pos[None, :] < kv_len
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None and window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
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


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, K, D)
    v: jnp.ndarray,  # (B, Sk, K, Dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,  # None: interpret unless on a TPU
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    Dv = v.shape[-1]
    assert H % K == 0
    group = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    # Pad both lengths up to whole blocks.  Padded query rows are computed
    # and sliced off; padded keys are masked by `kv_len` (under causal
    # masking with q_offset >= 0 they already sit past every real query,
    # but a full or offset attention would otherwise see them).
    pad_q, pad_k = (-Sq) % block_q, (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    n_q = (Sq + pad_q) // block_q
    n_k = (Sk + pad_k) // block_k

    # (B, H, S, D) layout for clean 2D blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        logit_cap=logit_cap,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=n_k,
        kv_len=Sk if pad_k else None,
    )

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, None, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((None, None, block_k, Dv), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, Dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)  # back to (B, Sq, H, Dv)
