"""Dispatch layer: models call these; we pick the Pallas TPU kernel or a
scalable pure-JAX path.

Three tiers per op:
  * Pallas kernel (TPU target; validated in interpret mode in tests);
  * chunked jnp implementation — same blockwise algorithm in pure jnp
    (lax.scan over KV blocks carrying the online-softmax state).  This is
    what the dry-run lowers (Pallas cannot lower to the CPU backend without
    interpret mode) and what CPU smoke training runs.  Differentiable.
  * naive reference in ref.py — ground truth for tests only.

Selection: TPU backend -> Pallas, always compiled (the kernels choose
interpret mode only off the TPU, and attention pads any length to the
kernel's block); otherwise chunked jnp.
"""

from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.util import inner_unroll

from . import ref
from .decode_attention import decode_attention_pallas
from .flash_attention import flash_attention_pallas
from .mamba2_ssd import ssd_pallas
from .mlstm_kernel import mlstm_pallas

NEG_INF = -1e30


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _attention_chunked_jnp(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, K, D)
    v: jnp.ndarray,
    *,
    causal: bool,
    window: Optional[int],
    logit_cap: Optional[float],
    q_offset: int,
    scale: float,
    block_k: int = 4096,
) -> jnp.ndarray:
    """Online-softmax attention, lax.scan over KV blocks.  Never materializes
    (Sq, Sk); peak temp is (B, H, Sq, block_k).  GQA via reshape (no repeat).
    Dv may differ from Dqk (MLA)."""
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    Dv = v.shape[-1]
    G = H // K
    block_k = min(block_k, Sk)
    # pad Sk to multiple of block
    pad = (-Sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkb = (Sk + pad) // block_k

    qg = (q * scale).reshape(B, Sq, K, G, D)
    kb = k.reshape(B, nkb, block_k, K, D)
    vb = v.reshape(B, nkb, block_k, K, Dv)
    q_pos = jnp.arange(Sq) + q_offset

    def body(carry, inp):
        m, l, acc = carry  # (B,Sq,K,G), (B,Sq,K,G), (B,Sq,K,G,D)
        kblk, vblk, jb = inp  # (B,bk,K,D), (B,bk,K,D), ()
        s = jnp.einsum("bqkgd,bskd->bqkgs", qg, kblk).astype(jnp.float32)
        if logit_cap is not None and logit_cap > 0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        k_pos = jb * block_k + jnp.arange(block_k)
        mask = k_pos[None, :] < Sk  # padding
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None and window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, :, None, None, :], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        # optional: bf16 probabilities for the PV matmul (fp32 accumulate) —
        # halves the dominant attention activation bytes, like TPU flash
        # kernels (env REPRO_ATTN_P_BF16; a §Perf lever)
        if os.environ.get("REPRO_ATTN_P_BF16") == "1":
            pv = jnp.einsum(
                "bqkgs,bskd->bqkgd",
                p.astype(jnp.bfloat16),
                vblk.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
        else:
            pv = jnp.einsum("bqkgs,bskd->bqkgd", p, vblk.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, K, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, K, G, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nkb)),
        unroll=inner_unroll(),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, Dv).astype(q.dtype)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_k: int = 4096,
) -> jnp.ndarray:
    """(B, Sq, H, D) x (B, Sk, K, D)^2 -> (B, Sq, H, D)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if _use_pallas():
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, logit_cap=logit_cap,
            q_offset=q_offset, scale=scale,
        )
    if q.shape[1] * k.shape[1] <= 256 * 256:
        return ref.mha_reference(
            q, k, v, causal=causal, window=window, logit_cap=logit_cap,
            q_offset=q_offset, scale=scale,
        )
    return _attention_chunked_jnp(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        q_offset=q_offset, scale=scale, block_k=block_k,
    )


def decode_attention(
    q: jnp.ndarray,  # (B, H, D)
    k_cache: jnp.ndarray,  # (B, S, K, D)
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,  # (B,)
    *,
    logit_cap: Optional[float] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """One-token attention against the KV cache.

    The jnp path is written reduction-style so that a sequence-sharded cache
    under pjit turns the softmax reductions into all-reduces (flash-decoding
    across the model axis without shard_map)."""
    if not _use_pallas():
        return ref.decode_attention_reference(
            q, k_cache, v_cache, cache_len,
            logit_cap=logit_cap, window=window, scale=scale,
        )
    return decode_attention_pallas(
        q, k_cache, v_cache, cache_len,
        logit_cap=logit_cap, window=window, scale=scale,
    )


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------

def _ssd_chunked_scan_jnp(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H)
    A: jnp.ndarray,  # (H,)
    Bmat: jnp.ndarray,  # (B, S, G, N)
    Cmat: jnp.ndarray,  # (B, S, G, N)
    D: Optional[jnp.ndarray],
    *,
    chunk: int,
    init_state: Optional[jnp.ndarray] = None,
):
    """Chunked SSD with lax.scan over chunks (state carried); peak temp is
    one chunk's (B, c, c, H) score tensor, vs the (B, nc, c, c, H) blow-up
    of the naive batched form in ref.py."""
    Bz, S, H, P = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    rep = H // G
    nc = S // chunk

    xf = x.astype(jnp.float32).reshape(Bz, nc, chunk, H, P).swapaxes(0, 1)
    dtf = dt.astype(jnp.float32).reshape(Bz, nc, chunk, H).swapaxes(0, 1)
    Bh = jnp.repeat(Bmat, rep, axis=2).astype(jnp.float32).reshape(
        Bz, nc, chunk, H, N
    ).swapaxes(0, 1)
    Ch = jnp.repeat(Cmat, rep, axis=2).astype(jnp.float32).reshape(
        Bz, nc, chunk, H, N
    ).swapaxes(0, 1)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(h, inp):
        xc, dtc, bc, cc = inp  # (B,c,H,P), (B,c,H), (B,c,H,N), (B,c,H,N)
        a = A[None, None, :] * dtc  # (B,c,H)
        a_cum = jnp.cumsum(a, axis=1)
        a_tot = a_cum[:, -1, :]  # (B,H)
        seg = a_cum[:, :, None, :] - a_cum[:, None, :, :]  # (B,t,s,H)
        L = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
        cb = jnp.einsum("bthk,bshk->btsh", cc, bc)
        scores = cb * L * dtc[:, None, :, :]
        y_intra = jnp.einsum("btsh,bshp->bthp", scores, xc)
        y_inter = jnp.einsum("bch,bchk,bhpk->bchp", jnp.exp(a_cum), cc, h)
        w = jnp.exp(a_tot[:, None, :] - a_cum) * dtc  # (B,c,H)
        new_contrib = jnp.einsum("bch,bchp,bchk->bhpk", w, xc, bc)
        h_new = h * jnp.exp(a_tot)[..., None, None] + new_contrib
        return h_new, y_intra + y_inter

    h0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((Bz, H, P, N), jnp.float32)
    )
    h_final, ys = jax.lax.scan(body, h0, (xf, dtf, Bh, Ch), unroll=inner_unroll())
    y = ys.swapaxes(0, 1).reshape(Bz, S, H, P)
    if D is not None:
        y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), h_final


def ssd_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    Bmat: jnp.ndarray,
    Cmat: jnp.ndarray,
    D: Optional[jnp.ndarray] = None,
    *,
    chunk: int = 128,
    return_state: bool = False,
):
    S = x.shape[1]
    if _use_pallas() and S % chunk == 0 and not return_state:
        return ssd_pallas(x, dt, A, Bmat, Cmat, D, chunk=chunk)
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:  # pad to chunk multiple (padded dt=0 -> identity steps)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, h_final = _ssd_chunked_scan_jnp(x, dt, A, Bmat, Cmat, D, chunk=chunk)
    y = y[:, :S] if pad else y
    if return_state:
        return y, h_final
    return y


def ssd_decode_step(
    state: jnp.ndarray,  # (B, H, P, N)
    x_t: jnp.ndarray,  # (B, H, P)
    dt_t: jnp.ndarray,  # (B, H)
    A: jnp.ndarray,  # (H,)
    B_t: jnp.ndarray,  # (B, G, N)
    C_t: jnp.ndarray,  # (B, G, N)
    D: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """O(1) recurrent step (long-context decode path)."""
    H = x_t.shape[1]
    G = B_t.shape[1]
    rep = H // G
    Bh = jnp.repeat(B_t, rep, axis=1)  # (B,H,N)
    Ch = jnp.repeat(C_t, rep, axis=1)
    decay = jnp.exp(A[None, :] * dt_t)  # (B,H)
    state = state * decay[..., None, None] + (
        (dt_t[..., None] * x_t)[..., None] * Bh[:, :, None, :]
    )
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    if D is not None:
        y = y + x_t * D[None, :, None]
    return state, y.astype(x_t.dtype)


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def _mlstm_chunked_jnp(
    q: jnp.ndarray,  # (B,S,H,D)
    k: jnp.ndarray,
    v: jnp.ndarray,
    i_gate: jnp.ndarray,  # (B,S,H)
    f_gate: jnp.ndarray,
    *,
    block_k: int = 2048,
) -> jnp.ndarray:
    """Blockwise stabilized mLSTM (same math as the Pallas kernel), scanning
    KV blocks with running (m, l, acc)."""
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    assert S % block_k == 0
    nkb = S // block_k

    fcum = jnp.cumsum(jax.nn.log_sigmoid(f_gate.astype(jnp.float32)), axis=1)
    qf = q.astype(jnp.float32) * scale
    kb = k.reshape(B, nkb, block_k, H, D)
    vb = v.reshape(B, nkb, block_k, H, D)
    fb = fcum.reshape(B, nkb, block_k, H)
    ib = i_gate.astype(jnp.float32).reshape(B, nkb, block_k, H)
    q_pos = jnp.arange(S)

    def body(carry, inp):
        m, l, acc = carry  # (B,S,H), (B,S,H), (B,S,H,D)
        kblk, vblk, fblk, iblk, jb = inp
        k_pos = jb * block_k + jnp.arange(block_k)
        mask = k_pos[None, :] <= q_pos[:, None]  # (S, bk)
        dmat = (
            fcum[:, :, None, :] - fblk[:, None, :, :] + iblk[:, None, :, :]
        )  # (B,S,bk,H)
        dmat = jnp.where(mask[None, :, :, None], dmat, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(dmat, axis=2))
        dexp = jnp.where(
            mask[None, :, :, None], jnp.exp(dmat - m_new[:, :, None, :]), 0.0
        )
        s = jnp.einsum("bqhd,bshd->bqsh", qf, kblk.astype(jnp.float32))
        w = s * dexp
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(w, axis=2)
        wv = jnp.einsum("bqsh,bshd->bqhd", w, vblk.astype(jnp.float32))
        acc_new = acc * corr[..., None] + wv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, H), jnp.float32)
    a0 = jnp.zeros((B, S, H, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (
            kb.swapaxes(0, 1),
            vb.swapaxes(0, 1),
            fb.swapaxes(0, 1),
            ib.swapaxes(0, 1),
            jnp.arange(nkb),
        ),
        unroll=inner_unroll(),
    )
    denom = jnp.maximum(jnp.abs(l), jnp.exp(-m))
    return (acc / denom[..., None]).astype(q.dtype)


def mlstm_parallel(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    i_gate: jnp.ndarray,
    f_gate: jnp.ndarray,
    *,
    block_k: int = 2048,
) -> jnp.ndarray:
    S = q.shape[1]
    if _use_pallas() and S % 128 == 0:
        return mlstm_pallas(q, k, v, i_gate, f_gate)
    if S <= 256:
        return ref.mlstm_reference(q, k, v, i_gate, f_gate)
    if S % block_k != 0:
        block_k = max(s for s in (128, 64, 32, 16, 8, 4, 2, 1) if S % s == 0)
    return _mlstm_chunked_jnp(q, k, v, i_gate, f_gate, block_k=block_k)


mlstm_decode_step = ref.mlstm_recurrent_step
slstm_scan = ref.slstm_reference
