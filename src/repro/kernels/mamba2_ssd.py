"""Mamba2 SSD (state-space dual) chunked scan for TPU (Pallas).

The SSD insight (Mamba2 paper): the selective-SSM recurrence decomposes into
(a) a *within-chunk* quadratic term — plain matmuls, perfect for the MXU —
and (b) a *cross-chunk* rank-1-ish state recurrence carried sequentially.

TPU adaptation (vs the Triton kernel in the Mamba2 release):
  * grid = (B, H, n_chunks) with the chunk dimension sequential; the running
    per-head state (P x N) persists in VMEM scratch across grid steps —
    no inter-CTA synchronization needed (Triton runs a separate state-passing
    kernel; the sequential TPU grid fuses all three phases in one kernel);
  * all within-chunk ops are (chunk x chunk) / (chunk x N) / (chunk x P)
    matmuls sized to MXU tiles (chunk defaults to 128);
  * gate cumsums are computed in fp32 in-kernel to avoid HBM round-trips
    for (B, S, H) intermediates — as a lower-triangular masked row sum,
    since Mosaic has no cumsum lowering.

Grouped B/C (G groups broadcast over H heads) is folded into index_maps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(
    x_ref,  # (chunk, P)
    dt_ref,  # (chunk, 1) dt as a column
    dtr_ref,  # (1, chunk) the same dt as a row
    a_ref,  # (1, 1)  per-head A (negative)
    b_ref,  # (chunk, N)
    c_ref,  # (chunk, N)
    d_ref,  # (1, 1)  per-head skip D (or zeros)
    y_ref,  # (chunk, P) output
    state_scr,  # (P, N) carried cross-chunk state
    *,
    chunk: int,
    num_chunks: int,
    has_d: bool,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    # Every vector stays 2-D: Mosaic lowers neither cumsum nor reductions to
    # rank 1, so the log-decay prefix sums are lower-triangular masked sums,
    # taken once per layout (column over lanes, row over sublanes).
    x = x_ref[...].astype(jnp.float32)  # (c, P)
    dt = dt_ref[...].astype(jnp.float32)  # (c, 1)
    dt_row = dtr_ref[...].astype(jnp.float32)  # (1, c)
    A = a_ref[...].astype(jnp.float32)  # (1, 1)
    Bm = b_ref[...].astype(jnp.float32)  # (c, N)
    Cm = c_ref[...].astype(jnp.float32)  # (c, N)

    a_col = A * dt  # (c, 1) log-decay increments
    a_row = A * dt_row  # (1, c)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = t_idx >= s_idx  # (t, s) lower triangle incl. diagonal
    a_cum = jnp.sum(jnp.where(tri, a_row, 0.0), axis=1, keepdims=True)  # (c, 1) inclusive
    a_cum_row = jnp.sum(jnp.where(t_idx <= s_idx, a_col, 0.0), axis=0, keepdims=True)  # (1, c)
    a_total = jnp.sum(a_row, axis=1, keepdims=True)  # (1, 1)

    # within-chunk quadratic term
    seg = a_cum - a_cum_row  # (t, s)
    L = jnp.where(tri, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (t, s)
    scores = cb * L * dt_row
    y_intra = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (t, P)

    # inter-chunk contribution from entering state
    c_decay = Cm * jnp.exp(a_cum)  # (t, N)
    y_inter = jax.lax.dot_general(
        c_decay,
        state_scr[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (t, P)

    y = y_intra + y_inter
    if has_d:
        y = y + x * d_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)

    # state update: h' = exp(a_total) h + sum_s exp(a_total - a_cum[s]) dt_s x_s B_s^T
    w = jnp.exp(a_total - a_cum) * dt  # (s, 1)
    xw = x * w  # (s, P)
    new_contrib = jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    state_scr[...] = state_scr[...] * jnp.exp(a_total) + new_contrib


def ssd_pallas(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H)
    A: jnp.ndarray,  # (H,)
    Bmat: jnp.ndarray,  # (B, S, G, N)
    Cmat: jnp.ndarray,  # (B, S, G, N)
    D: Optional[jnp.ndarray] = None,  # (H,)
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,  # None: interpret unless on a TPU
) -> jnp.ndarray:
    Bz, S, H, P = x.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    G, N = Bmat.shape[2], Bmat.shape[3]
    assert H % G == 0
    rep = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    xt = x.transpose(0, 2, 1, 3)  # (B, H, S, P)
    dtt = dt.transpose(0, 2, 1)[..., None]  # (B, H, S, 1)
    dtr = dt.transpose(0, 2, 1)[:, :, None, :]  # (B, H, 1, S)
    bt = Bmat.transpose(0, 2, 1, 3)  # (B, G, S, N)
    ct = Cmat.transpose(0, 2, 1, 3)
    a2 = A.reshape(H, 1, 1).astype(jnp.float32)
    d2 = (D if D is not None else jnp.zeros((H,), jnp.float32)).reshape(H, 1, 1)

    kernel = functools.partial(
        _ssd_kernel, chunk=chunk, num_chunks=nc, has_d=D is not None
    )
    out = pl.pallas_call(
        kernel,
        grid=(Bz, H, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((None, 1, 1), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((None, None, chunk, N), lambda b, h, c: (b, h // rep, c, 0)),
            pl.BlockSpec((None, None, chunk, N), lambda b, h, c: (b, h // rep, c, 0)),
            pl.BlockSpec((None, 1, 1), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bz, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mamba2_ssd",
    )(xt, dtt, dtr, a2, bt, ct, d2)
    return out.transpose(0, 2, 1, 3)  # (B, S, H, P)
