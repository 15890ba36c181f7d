"""mLSTM (xLSTM matrix-memory cell) parallel form for TPU (Pallas).

The stabilized parallel mLSTM is flash-attention-shaped: a lower-triangular
gate matrix D_ts = exp(F_t - F_s + i_s - m_t) replaces softmax, and the
normalizer is max(|row-sum|, exp(-m_t)) instead of the softmax denominator.
The same online-rescaling trick applies, with two twists:
  * the running stabilizer m tracks the max of the *gate* exponent (not the
    score), so it is independent of q·k and can be rescaled identically;
  * the accumulated denominator is a *signed* sum (scores are not
    exponentiated), so the final clamp uses |l|.

Gate cumsums F = cumsum(log-sigmoid f) are precomputed outside (cheap,
(B,S,H)) and streamed in per block — recomputing cross-block prefix sums
inside the kernel would serialize the parallel grid dims.
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


def _mlstm_kernel(
    q_ref,  # (bq, D)
    k_ref,  # (bk, D)
    v_ref,  # (bk, D)
    fcum_q_ref,  # (bq, 1) F at query positions
    fcum_k_ref,  # (bk, 1) F at key positions
    i_ref,  # (bk, 1) input-gate preact at key positions
    o_ref,  # (bq, D)
    m_scr,  # (bq,)
    l_scr,  # (bq,)
    acc_scr,  # (bq, D)
    *,
    scale: float,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = kj * block_k <= qi * block_q + block_q - 1  # causal block skip

    @pl.when(live)
    def _compute():
        q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
        k_pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = k_pos[None, :] <= q_pos[:, None]

        fq = fcum_q_ref[...].astype(jnp.float32)[:, 0]  # (bq,)
        fk = fcum_k_ref[...].astype(jnp.float32)[:, 0]  # (bk,)
        ig = i_ref[...].astype(jnp.float32)[:, 0]  # (bk,)
        dmat = fq[:, None] - fk[None, :] + ig[None, :]  # (bq, bk)
        dmat = jnp.where(mask, dmat, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(dmat, axis=1))
        corr = jnp.exp(m_prev - m_new)
        dexp = jnp.where(mask, jnp.exp(dmat - m_new[:, None]), 0.0)

        s = jax.lax.dot_general(
            q_ref[...].astype(jnp.float32) * scale,
            k_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        w = s * dexp  # signed weights
        l_scr[...] = l_scr[...] * corr + jnp.sum(w, axis=1)
        wv = jax.lax.dot_general(
            w,
            v_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr[:, None] + wv
        m_scr[...] = m_new

    @pl.when(kj == num_k_blocks - 1)
    def _flush():
        denom = jnp.maximum(jnp.abs(l_scr[...]), jnp.exp(-m_scr[...]))
        o_ref[...] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def mlstm_pallas(
    q: jnp.ndarray,  # (B, S, H, D)
    k: jnp.ndarray,
    v: jnp.ndarray,
    i_gate: jnp.ndarray,  # (B, S, H)
    f_gate: jnp.ndarray,  # (B, S, H)
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,  # None: interpret unless on a TPU
) -> jnp.ndarray:
    B, S, H, D = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    n_q, n_k = S // block_q, S // block_k

    fcum = jnp.cumsum(
        jax.nn.log_sigmoid(f_gate.astype(jnp.float32)), axis=1
    )  # (B,S,H)

    qt = q.transpose(0, 2, 1, 3)  # (B,H,S,D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ft = fcum.transpose(0, 2, 1)[..., None]  # (B,H,S,1)
    it = i_gate.astype(jnp.float32).transpose(0, 2, 1)[..., None]

    kernel = functools.partial(
        _mlstm_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=n_k,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, None, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_k, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((None, None, block_k, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_k, 1), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((None, None, block_k, 1), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mlstm_parallel",
    )(qt, kt, vt, ft, ft, it)
    return out.transpose(0, 2, 1, 3)
