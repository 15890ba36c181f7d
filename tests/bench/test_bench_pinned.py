"""Numbers of the Qwen3 binding that must not move.

Read on the CPU from the harness as it was before its Qwen3 code (weights,
reference, parameter layout and op counts) moved from `bench/lib` into
`bench/arch/qwen3`.  Every reading of the benchmark rests on them: a count
that changes, or a weight or reference logit that does, changes
`step_mfu.chat`, `decode_attention_roofline` or `logit_gap` with it.
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest
import tinybench

from bench import arch
from bench.lib import system

SEED = 2**33 + 3

COUNTS = {
    "qwen3-1.7b": {
        "decode_token_flops": {1: 3441131520.0, 512: 3558342656.0, 2560: 4028104704.0},
        "prefill_flops": {64: 181488058368.0, 512: 1473854832640.0, 2048: 6254329593856.0},
        "decode_attention_cost": {"flops": 138084352.0, "bytes": 69959680.0},
        "flash_attention_cost": {"flops": 30123491328.0, "bytes": 176160768},
    },
    "qwen3-32b": {
        "decode_token_flops": {1: 9357492224.0, 512: 9491447808.0, 2560: 10028318720.0},
        "prefill_flops": {64: 501391032320.0, 512: 4030302257152.0, 2048: 16528858415104.0},
        "decode_attention_cost": {"flops": 157810688.0, "bytes": 20774912.0},
        "flash_attention_cost": {"flops": 34426847232.0, "bytes": 150994944},
    },
}

# per leaf of tinybench's weights at SEED: sum and sum of squares, in float64
WEIGHT_SUMS = {
    "['embed']": (3.115962564945221, 515.1266457921126),
    "['final_norm']": (64.2109375, 65.03201293945312),
    "['layers']['attn_norm']": (127.71484375, 128.28529357910156),
    "['layers']['k_norm']": (32.33203125, 32.98826599121094),
    "['layers']['mlp_norm']": (127.3671875, 128.24649047851562),
    "['layers']['q_norm']": (31.265625, 30.839385986328125),
    "['layers']['w_down']": (-19.722267724573612, 255.72575179352282),
    "['layers']['w_gate']": (-11.78863251209259, 257.3285792749106),
    "['layers']['w_up']": (13.319813013076782, 256.41375391484087),
    "['layers']['wk']": (3.199434995651245, 65.85001936776058),
    "['layers']['wo']": (-4.040301911532879, 127.12836523613845),
    "['layers']['wq']": (1.0175127470865846, 127.6941511215042),
    "['layers']['wv']": (-11.439513444900513, 62.51469588178935),
    "['lm_head']": (-34.009865775704384, 508.8684541174569),
}

# the reference's logits over 40 tokens at every position: sum, sum of |x|
LOGIT_SUMS = {
    None: (-111.29068971183733, 16453.423607527075),
    "fp8": (-136.22822929173708, 16375.50973881036),
}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_op_counts_are_pinned(name):
    with open(os.path.join(tinybench.ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    b = arch.for_config(cfg)
    want = COUNTS[name]
    assert {n: b.decode_token_flops(cfg, n) for n in want["decode_token_flops"]} == want["decode_token_flops"]
    assert {n: b.prefill_flops(cfg, n) for n in want["prefill_flops"]} == want["prefill_flops"]
    assert b.decode_attention_cost(cfg, [100, 200, 101, 201]) == want["decode_attention_cost"]
    assert b.flash_attention_cost(cfg, 512) == want["flash_attention_cost"]


def test_weights_are_pinned():
    w = system.reference_weights(tinybench.config(), SEED, jax.devices()[0])
    got = {}
    for path, x in jax.tree_util.tree_flatten_with_path(w)[0]:
        x = np.asarray(x, np.float64)
        got[jax.tree_util.keystr(path)] = (x.sum(), (x * x).sum())
    assert set(got) == set(WEIGHT_SUMS)
    for k, (s, s2) in WEIGHT_SUMS.items():
        assert got[k] == (pytest.approx(s, rel=1e-9, abs=1e-9), pytest.approx(s2, rel=1e-9)), k


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_reference_logits_are_pinned(quant):
    cfg = tinybench.config()
    w = system.reference_weights(cfg, SEED, jax.devices()[0])
    tokens = np.random.default_rng(0).integers(0, 512, 40).tolist()
    lg = np.asarray(arch.for_config(cfg).logits_at(w, cfg, tokens, np.arange(40), quant=quant), np.float64)
    s, s_abs = LOGIT_SUMS[quant]
    assert lg.sum() == pytest.approx(s, rel=1e-6)
    assert np.abs(lg).sum() == pytest.approx(s_abs, rel=1e-6)
