"""Each architecture binding's float32 reference against the serving engine.

At the binding's tiny size (`TINY`) on the CPU, the logits that
`ContinuousEngine` computes while it prefills a prompt and then decodes
through its bf16 cache, with a second request admitted into the running
batch, agree with the reference's full forward pass over prompt + served
tokens, within the binding's tolerance.  Every binding under `bench/arch`
is tested; a new one needs nothing here.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
import tinybench  # noqa: F401  (puts the repository root on sys.path)

from bench import arch
from bench.lib import system, weights

BINDINGS = arch.names()
CASES = [(t, c) for t in BINDINGS for c in arch.binding(t).TINY["cases"]]


def _tiny(model_type, case=None):
    """tinybench's engine and limits with the binding's tiny model."""
    tiny = arch.binding(model_type).TINY
    return tinybench.config(**{**tiny["config"], **(tiny["cases"][case] if case else {})})


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


@pytest.mark.parametrize("model_type,case", CASES, ids=[f"{t}-{c}" for t, c in CASES])
def test_reference_matches_engine_prefill_then_decode(model_type, case):
    cfg = _tiny(model_type, case)
    binding = arch.for_config(cfg)
    vocab = int(cfg["vocab_size"])
    rng = np.random.default_rng(0)
    prompts = {"a": rng.integers(0, vocab, 21).tolist(), "b": rng.integers(0, vocab, 35).tolist()}
    served = _serve_recording(cfg, seed=2**33 + 3, prompts=prompts, max_new=9, admit_after=3)
    w = system.reference_weights(cfg, 2**33 + 3, jax.devices()[0])
    for r, (out, got) in served.items():
        seq = prompts[r] + out[:-1]
        rows = np.arange(len(prompts[r]) - 1, len(seq))
        ref = np.asarray(binding.logits_at(w, cfg, seq, rows))
        assert got.shape == ref.shape
        err = np.abs(got - ref).max()
        assert err < binding.TINY["atol"], (r, err, binding.TINY["why"])
        assert ref.std() > 0.5  # logits of unit spread: the tolerance means something


@pytest.mark.parametrize("model_type", BINDINGS)
def test_reference_weights_are_the_served_weights(model_type):
    """The engine's parameters and the reference's come from one draw:
    every parameter is the binding's layout of the reference's weights."""
    cfg = _tiny(model_type)
    binding = arch.for_config(cfg)
    dev = jax.devices()[0]
    w = system.reference_weights(cfg, 77, dev)
    p = system.program_params(cfg, 77, dev)
    laid = jax.jit(lambda w: binding.to_program(w, cfg))(w)
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(laid)
    for a, b in zip(jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(laid)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_qwen3_norms_are_served_as_offsets():
    """Qwen3's program layout: heads split out of the projections, and
    norm weights stored as offsets from 1, exactly."""
    cfg = _tiny("qwen3")
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
