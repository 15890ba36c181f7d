"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + property tests.

All Pallas kernels run in interpret mode on CPU (the TPU target cannot
execute here); the chunked-jnp production paths are validated against the
same oracles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba2_ssd import ssd_pallas
from repro.kernels.mlstm_kernel import mlstm_pallas


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Sk, H, K, D, causal, window, cap, q_offset
    (2, 128, 128, 4, 4, 64, True, None, None, 0),
    (1, 256, 256, 8, 2, 64, True, None, None, 0),      # GQA 4:1
    (1, 128, 128, 4, 1, 128, True, None, None, 0),     # MQA
    (2, 128, 128, 4, 2, 32, True, 64, None, 0),        # sliding window
    (1, 128, 128, 2, 2, 64, True, None, 50.0, 0),      # softcap (gemma2)
    (1, 128, 256, 4, 4, 64, True, None, None, 128),    # continuation offset
    (1, 128, 128, 2, 1, 64, False, None, None, 0),     # encoder (full)
    (2, 100, 100, 4, 2, 64, True, None, None, 0),      # length not a block multiple
    (1, 72, 200, 2, 1, 64, False, None, None, 0),      # full attention, padded keys
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_pallas_vs_ref(case, dtype):
    B, Sq, Sk, H, K, D, causal, window, cap, off = case
    rng = np.random.default_rng(hash(case) % 2**31)
    q = _rand(rng, (B, Sq, H, D), dtype)
    k = _rand(rng, (B, Sk, K, D), dtype)
    v = _rand(rng, (B, Sk, K, D), dtype)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, logit_cap=cap, q_offset=off,
        block_q=64, block_k=64,
    )
    exp = ref.mha_reference(
        q, k, v, causal=causal, window=window, logit_cap=cap, q_offset=off
    )
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=tol, rtol=tol)


@given(
    st.integers(1, 2), st.sampled_from([64, 128, 192]), st.sampled_from([1, 2, 4]),
    st.sampled_from([32, 64]), st.booleans(),
)
@settings(max_examples=12, deadline=None)
def test_chunked_attention_matches_ref(B, S, K, D, causal):
    H = K * 2
    rng = np.random.default_rng(0)
    q = _rand(rng, (B, S, H, D))
    k = _rand(rng, (B, S, K, D))
    v = _rand(rng, (B, S, K, D))
    out = ops._attention_chunked_jnp(
        q, k, v, causal=causal, window=None, logit_cap=None, q_offset=0,
        scale=D**-0.5, block_k=64,
    )
    exp = ref.mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("impl", ["ops", "pallas"])
def test_attention_mla_head_dims(impl):
    """Dv != Dqk (MLA): the jnp path and the kernel must both handle it."""
    rng = np.random.default_rng(1)
    q = _rand(rng, (2, 300, 8, 192))
    k = _rand(rng, (2, 300, 8, 192))
    v = _rand(rng, (2, 300, 8, 128))
    if impl == "ops":
        out = ops.flash_attention(q, k, v, causal=True)
    else:
        out = flash_attention_pallas(q, k, v, causal=True)
    exp = ref.mha_reference(q, k, v, causal=True)
    assert out.shape == (2, 300, 8, 128)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=1e-4)


def test_flash_attention_grad_finite():
    rng = np.random.default_rng(2)
    q = _rand(rng, (1, 384, 4, 32))
    k = _rand(rng, (1, 384, 2, 32))
    v = _rand(rng, (1, 384, 2, 32))

    def loss(q):
        return jnp.sum(ops.flash_attention(q, k, v, causal=True, block_k=128) ** 2)

    g = jax.grad(loss)(q)
    assert bool(jnp.all(jnp.isfinite(g)))


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,H,K,D,window,cap", [
    (256, 8, 2, 64, None, None),
    (512, 4, 4, 32, None, None),
    (256, 8, 1, 128, 64, None),
    (256, 4, 2, 64, None, 30.0),
])
def test_decode_attention_pallas_vs_ref(S, H, K, D, window, cap):
    B = 3
    rng = np.random.default_rng(S + H)
    q = _rand(rng, (B, H, D))
    kc = _rand(rng, (B, S, K, D))
    vc = _rand(rng, (B, S, K, D))
    clen = jnp.asarray([S, S // 2, 17], jnp.int32)
    out = decode_attention_pallas(q, kc, vc, clen, window=window, logit_cap=cap, block_k=128)
    exp = ref.decode_attention_reference(q, kc, vc, clen, window=window, logit_cap=cap)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("S,block_k", [
    (97, 32),    # prime cache length, partial final block
    (300, 256),  # the old `assert S % block_k == 0` crash shape
    (130, 64),
])
def test_decode_attention_pallas_partial_block(S, block_k):
    """Arbitrary max_len values: the final partial cache block is padded and
    masked instead of tripping an assert."""
    B, H, K, D = 2, 4, 2, 32
    rng = np.random.default_rng(S)
    q = _rand(rng, (B, H, D))
    kc = _rand(rng, (B, S, K, D))
    vc = _rand(rng, (B, S, K, D))
    clen = jnp.asarray([S, S // 3], jnp.int32)
    out = decode_attention_pallas(q, kc, vc, clen, block_k=block_k)
    exp = ref.decode_attention_reference(q, kc, vc, clen)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,H,P,G,N,chunk", [
    (128, 4, 16, 2, 8, 32),
    (256, 2, 32, 1, 16, 64),
    (192, 8, 8, 4, 4, 64),   # pad path for jnp (192 % 64 == 0 though)
])
def test_ssd_pallas_vs_sequential(S, H, P, G, N, chunk):
    B = 2
    rng = np.random.default_rng(S)
    x = _rand(rng, (B, S, H, P))
    dt = jax.nn.softplus(_rand(rng, (B, S, H)))
    A = -jnp.exp(_rand(rng, (H,)))
    Bm = _rand(rng, (B, S, G, N))
    Cm = _rand(rng, (B, S, G, N))
    D = _rand(rng, (H,))
    out = ssd_pallas(x, dt, A, Bm, Cm, D, chunk=chunk)
    exp = ref.ssd_reference(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(out, exp, atol=5e-4, rtol=1e-3)


@given(st.integers(1, 3), st.sampled_from([60, 100, 128]))
@settings(max_examples=8, deadline=None)
def test_ssd_jnp_chunked_pad_path(B, S):
    """ops.ssd_scan must be exact also when S is not a chunk multiple."""
    H, P, G, N = 2, 8, 1, 4
    rng = np.random.default_rng(B * S)
    x = _rand(rng, (B, S, H, P))
    dt = jax.nn.softplus(_rand(rng, (B, S, H)))
    A = -jnp.exp(_rand(rng, (H,)))
    Bm = _rand(rng, (B, S, G, N))
    Cm = _rand(rng, (B, S, G, N))
    out = ops.ssd_scan(x, dt, A, Bm, Cm, None, chunk=32)
    exp = ref.ssd_reference(x, dt, A, Bm, Cm, None)
    np.testing.assert_allclose(out, exp, atol=5e-4, rtol=1e-3)


def test_ssd_decode_step_matches_scan():
    B, S, H, P, G, N = 2, 24, 4, 8, 2, 4
    rng = np.random.default_rng(7)
    x = _rand(rng, (B, S, H, P))
    dt = jax.nn.softplus(_rand(rng, (B, S, H)))
    A = -jnp.exp(_rand(rng, (H,)))
    Bm = _rand(rng, (B, S, G, N))
    Cm = _rand(rng, (B, S, G, N))
    y_seq = ref.ssd_reference(x, dt, A, Bm, Cm, None)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        state, y = ops.ssd_decode_step(state, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_seq, atol=1e-4, rtol=1e-3)


def test_ssd_prefill_state_continues_decode():
    """State returned by ssd_scan(return_state=True) must seamlessly continue."""
    B, S, H, P, G, N = 1, 64, 2, 8, 1, 4
    rng = np.random.default_rng(9)
    x = _rand(rng, (B, S + 8, H, P))
    dt = jax.nn.softplus(_rand(rng, (B, S + 8, H)))
    A = -jnp.exp(_rand(rng, (H,)))
    Bm = _rand(rng, (B, S + 8, G, N))
    Cm = _rand(rng, (B, S + 8, G, N))
    full = ref.ssd_reference(x, dt, A, Bm, Cm, None)
    _, state = ops.ssd_scan(
        x[:, :S], dt[:, :S], A, Bm[:, :S], Cm[:, :S], None, chunk=32, return_state=True
    )
    outs = []
    for t in range(S, S + 8):
        state, y = ops.ssd_decode_step(state, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        outs.append(y)
    np.testing.assert_allclose(jnp.stack(outs, 1), full[:, S:], atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,H,D,bq,bk", [
    (128, 2, 32, 64, 64),
    (256, 4, 16, 128, 64),
])
def test_mlstm_pallas_vs_ref(S, H, D, bq, bk):
    B = 2
    rng = np.random.default_rng(S + D)
    q = _rand(rng, (B, S, H, D))
    k = _rand(rng, (B, S, H, D))
    v = _rand(rng, (B, S, H, D))
    ig = _rand(rng, (B, S, H))
    fg = _rand(rng, (B, S, H)) + 2.0
    out = mlstm_pallas(q, k, v, ig, fg, block_q=bq, block_k=bk)
    exp = ref.mlstm_reference(q, k, v, ig, fg)
    np.testing.assert_allclose(out, exp, atol=5e-4, rtol=1e-3)


def test_mlstm_chunked_jnp_matches_ref():
    B, S, H, D = 1, 512, 2, 16
    rng = np.random.default_rng(11)
    q = _rand(rng, (B, S, H, D))
    k = _rand(rng, (B, S, H, D))
    v = _rand(rng, (B, S, H, D))
    ig = _rand(rng, (B, S, H))
    fg = _rand(rng, (B, S, H)) + 1.0
    out = ops._mlstm_chunked_jnp(q, k, v, ig, fg, block_k=128)
    exp = ref.mlstm_reference(q, k, v, ig, fg)
    np.testing.assert_allclose(out, exp, atol=5e-4, rtol=1e-3)


def test_mlstm_recurrent_matches_parallel():
    B, S, H, D = 2, 48, 2, 8
    rng = np.random.default_rng(13)
    q = _rand(rng, (B, S, H, D))
    k = _rand(rng, (B, S, H, D))
    v = _rand(rng, (B, S, H, D))
    ig = _rand(rng, (B, S, H))
    fg = _rand(rng, (B, S, H)) + 1.0
    par = ref.mlstm_reference(q, k, v, ig, fg)
    c = jnp.zeros((B, H, D, D))
    n = jnp.zeros((B, H, D))
    m = jnp.full((B, H), -1e9)
    outs = []
    for t in range(S):
        (c, n, m), h = ops.mlstm_decode_step(c, n, m, q[:, t], k[:, t], v[:, t], ig[:, t], fg[:, t])
        outs.append(h)
    np.testing.assert_allclose(jnp.stack(outs, 1), par, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# properties
# ---------------------------------------------------------------------------

@given(st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_attention_is_permutation_invariant_over_batch(seed):
    """Property: attention over batch rows is independent."""
    rng = np.random.default_rng(seed)
    B, S, H, D = 4, 64, 2, 16
    q = _rand(rng, (B, S, H, D))
    k = _rand(rng, (B, S, H, D))
    v = _rand(rng, (B, S, H, D))
    out = ref.mha_reference(q, k, v, causal=True)
    perm = np.asarray([2, 0, 3, 1])
    out_p = ref.mha_reference(q[perm], k[perm], v[perm], causal=True)
    np.testing.assert_allclose(out[perm], out_p, atol=1e-6)


@given(st.floats(1.0, 100.0))
@settings(max_examples=10, deadline=None)
def test_softcap_bounds_logits(cap):
    x = jnp.linspace(-1e4, 1e4, 64)
    y = ref.softcap(x, cap)
    assert float(jnp.max(jnp.abs(y))) <= cap * (1 + 1e-6)
