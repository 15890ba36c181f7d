"""Plain Qwen3 forward pass in float32: the yardstick of `correct`.

Written from the published description of Qwen3 (the `Qwen3ForCausalLM`
of Hugging Face transformers and the Qwen3 technical report,
arXiv:2505.09388), not from the program under test, which it never
imports.  Per layer, with RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g:

  x = RMSNorm(h);  q, k, v = x Wq, x Wk, x Wv   (H query, K key/value heads)
  q, k = RMSNorm per head (q_norm, k_norm), then rotary embedding
         (rotate-half, inv_freq = theta^(-2i/hd))
  attention causal, softmax(q k^T / sqrt(hd)), query head j reads
         key/value head j // (H / K)
  h = h + attn Wo;  x = RMSNorm(h);  h = h + (silu(x Wg) * x Wu) Wd
  logits = RMSNorm(h) @ head   (head = embed^T where tied)

No cache, no kernels, no batching.  Departures from the published model:
none in the equations; the weights are random (`weights.py`) and the
computation is float32 with `default_matmul_precision("highest")`.

`quant` computes the same pass in a lower precision, as the control of
the comparison: weights rounded to int8 or to float8 e4m3
(`bench/lib/weights.quantize`), activations and products in bfloat16.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.weights import quantize

from .weights import dims, head

Q_BLOCK = 256  # query rows per attention block (bounds the score matrix)
SEQ_MULTIPLE = 512  # sequences pad to this, so that few shapes compile
V_BLOCKS = 8  # vocabulary blocks of the output head


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]  # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    rot = jnp.concatenate([-x2, x1], -1)
    xf = x.astype(jnp.float32)
    return (xf * cos + rot.astype(jnp.float32) * sin).astype(x.dtype)


def _layer(h, lw, *, config, pos, act_dtype, quant):
    d = dims(config)
    H, K, hd = d["H"], d["K"], d["hd"]
    eps = float(config["rms_norm_eps"])
    S = h.shape[0]

    def mat(name):
        w = lw[name]
        return quantize(w, quant) if quant else w.astype(act_dtype)

    def mm(x, w):
        return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(act_dtype)

    x = _rms(h, lw["attn_norm"], eps)
    q = mm(x, mat("wq")).reshape(S, H, hd)
    k = mm(x, mat("wk")).reshape(S, K, hd)
    v = mm(x, mat("wv")).reshape(S, K, hd)
    q = _rope(_rms(q, lw["q_norm"], eps), pos, float(config["rope_theta"]))
    k = _rope(_rms(k, lw["k_norm"], eps), pos, float(config["rope_theta"]))
    group = H // K
    k = jnp.repeat(k, group, axis=1)  # query head j reads kv head j // group
    v = jnp.repeat(v, group, axis=1)
    scale = 1.0 / (hd**0.5)
    nq = S // Q_BLOCK

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, preferred_element_type=jnp.float32) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(jnp.arange(S)[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(act_dtype)
        return jnp.einsum("hqk,khd->qhd", p, v, preferred_element_type=jnp.float32)

    att = jax.lax.map(attend, jnp.arange(nq)).reshape(S, H * hd).astype(act_dtype)
    h = h + mm(att, mat("wo"))
    x = _rms(h, lw["mlp_norm"], eps)
    gate = jax.nn.silu(mm(x, mat("w_gate")).astype(jnp.float32))
    up = mm(x, mat("w_up")).astype(jnp.float32)
    return h + mm((gate * up).astype(act_dtype), mat("w_down"))


@partial(jax.jit, static_argnames=("config_items", "quant"))
def _final_hidden(w, tokens, *, config_items, quant):
    config = dict(config_items)
    act = jnp.bfloat16 if quant else jnp.float32
    S = tokens.shape[0]
    pos = jnp.arange(S)
    h = w["embed"][tokens].astype(act)

    def body(hh, lw):
        if not quant:
            lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        return _layer(hh, lw, config=config, pos=pos, act_dtype=act, quant=quant), None

    h, _ = jax.lax.scan(body, h, w["layers"])
    return _rms(h, w["final_norm"].astype(jnp.float32), float(config["rms_norm_eps"]))


@partial(jax.jit, static_argnames=("quant",))
def _logits(w, hn, rows, *, quant):
    """Logits at `rows` of `hn`, computed over vocabulary blocks."""
    x = hn[rows]
    W = head(w)
    V = W.shape[1]
    nb = V_BLOCKS if V % V_BLOCKS == 0 else 1
    Wb = W.reshape(W.shape[0], nb, V // nb).transpose(1, 0, 2)

    def one(wb):
        if quant:
            return jnp.dot(
                x.astype(jnp.bfloat16), quantize(wb, quant),
                preferred_element_type=jnp.float32,
            )
        return jnp.dot(x.astype(jnp.float32), wb.astype(jnp.float32))

    return jax.lax.map(one, Wb).transpose(1, 0, 2).reshape(x.shape[0], V)


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def logits_at(
    w: Dict[str, Any],
    config: Dict[str, Any],
    tokens,
    rows,
    *,
    quant: Optional[str] = None,
) -> jax.Array:
    """(len(rows), V) float32 logits of the sequence `tokens` at `rows`.

    The sequence is right-padded to a multiple of `SEQ_MULTIPLE` (causal
    attention keeps the padding out of every earlier position)."""
    toks = np.asarray(tokens, np.int32)
    S = _pad(max(len(toks), 1), SEQ_MULTIPLE)
    padded = np.zeros((S,), np.int32)
    padded[: len(toks)] = toks
    rows = np.asarray(rows, np.int32)
    P = _pad(len(rows), 128)
    rows_p = np.full((P,), rows[-1] if len(rows) else 0, np.int32)
    rows_p[: len(rows)] = rows
    items = tuple(sorted((k, v) for k, v in config.items() if isinstance(v, (int, float, bool, str))))
    if quant is None:
        with jax.default_matmul_precision("highest"):
            hn = _final_hidden(w, jnp.asarray(padded), config_items=items, quant=None)
            out = _logits(w, hn, jnp.asarray(rows_p), quant=None)
    else:
        hn = _final_hidden(w, jnp.asarray(padded), config_items=items, quant=quant)
        out = _logits(w, hn, jnp.asarray(rows_p), quant=quant)
    return out[: len(rows)]
