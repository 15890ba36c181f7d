"""The benchmark's float32 Qwen3 reference against the serving engine.

At a tiny width on the CPU, the logits that `ContinuousEngine` computes
while it prefills a prompt and then decodes through its bf16 cache, with a
second request admitted into the running batch, agree with the
reference's full forward pass over prompt + served tokens.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
import tinybench  # noqa: F401  (puts the repository root on sys.path)

from bench.lib import reference, system, weights

# The engine holds activations, the KV cache and its matmul inputs in bf16
# and rounds the head's output to bf16 before widening it (half an ulp is
# 0.008 at a logit of 4).  Measured at this size: largest error 0.065 over
# both requests and both head layouts.  0.15 leaves room for that and is
# far below an error of the mechanism (a wrong position, mask, norm or
# head gives errors of O(1) at logits of unit spread).
ATOL = 0.15


def _serve_recording(cfg, seed, prompts, max_new, admit_after):
    eng = system.build_engine(cfg, seed, jax.devices()[0])
    logs = {r: [] for r in prompts}
    pre, dec = eng._prefill, eng._decode
    pending = []

    def prefill(params, batch, cache):
        out = pre(params, batch, cache)
        pending.append(np.asarray(out[0])[0])  # (L_pad, V)
        return out

    def decode(params, toks, cache, lens):
        out = dec(params, toks, cache, lens)
        lg = np.asarray(out[0])[:, 0]
        for i, s in enumerate(eng.slots):
            if s is not None and not s.done:
                logs[s.req_id].append(lg[i])
        return out

    eng._prefill, eng._decode = prefill, decode
    ids = list(prompts)
    finished = {}

    def admit(r):
        eng.admit([(r, prompts[r], max_new)])
        logs[r].append(pending.pop()[len(prompts[r]) - 1])

    admit(ids[0])
    for _ in range(admit_after):
        done, _ = eng.step_chunk(1)
        finished.update(done)
    for r in ids[1:]:
        admit(r)
    while eng.n_live():
        done, _ = eng.step_chunk()
        finished.update(done)
    assert eng.stats["mid_batch_admissions"] == len(ids) - 1
    return {r: (finished[r].out, np.stack(logs[r][: max_new])) for r in ids}


@pytest.mark.parametrize("tied", [False, True])
def test_reference_matches_engine_prefill_then_decode(tied):
    cfg = tinybench.config(tie_word_embeddings=tied)
    rng = np.random.default_rng(0)
    prompts = {"a": rng.integers(0, 512, 21).tolist(), "b": rng.integers(0, 512, 35).tolist()}
    served = _serve_recording(cfg, seed=2**33 + 3, prompts=prompts, max_new=9, admit_after=3)
    w = system.reference_weights(cfg, 2**33 + 3, jax.devices()[0])
    for r, (out, got) in served.items():
        seq = prompts[r] + out[:-1]
        rows = np.arange(len(prompts[r]) - 1, len(seq))
        ref = np.asarray(reference.logits_at(w, cfg, seq, rows))
        assert got.shape == ref.shape
        err = np.abs(got - ref).max()
        assert err < ATOL, (r, err)
        assert ref.std() > 0.5  # logits of unit spread: the tolerance means something


def test_reference_weights_are_the_served_weights():
    """The engine's parameters and the reference's come from one draw."""
    cfg = tinybench.config()
    dev = jax.devices()[0]
    w = system.reference_weights(cfg, 77, dev)
    p = system.program_params(cfg, 77, dev)
    np.testing.assert_array_equal(
        np.asarray(p["decoder"]["attn"]["wq"][1, 0]).reshape(64, -1), np.asarray(w["layers"]["wq"][1])
    )
    g = np.asarray(w["layers"]["q_norm"][0], np.float32)
    np.testing.assert_array_equal(1.0 + np.asarray(p["decoder"]["attn"]["q_norm"][0, 0], np.float32), g)
    assert not np.allclose(g, 1.0)


def test_seed_key_takes_large_seeds():
    a, b = weights.seed_key(2**31 + 5), weights.seed_key(2**33 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(weights.seed_key(2**31 + 5)))
