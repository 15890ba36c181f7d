"""A tiny Qwen3-shaped configuration and mixes for the benchmark's CPU tests."""

from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = {
    "name": "tiny-qwen3",
    "model_type": "qwen3",
    "num_hidden_layers": 2,
    "hidden_size": 64,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "intermediate_size": 128,
    "vocab_size": 512,
    "rope_theta": 1000000,
    "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    # 1/sqrt(hidden): logits of unit spread, so rounding shows in them
    "initializer_range": 0.125,
    "engine": {
        "max_batch": 4,
        "max_len": 128,
        "prefill_bucket": 16,
        "decode_chunk": 4,
        "cache_dtype": "bfloat16",
        "lease_timeout_s": 30,
        "heartbeat_interval_s": 0.5,
    },
    # between the program's readings and the control's at this size (see
    # test_bench_control.py)
    "limits": {"logit_gap": 0.08, "sample_tokens": 64, "sample_requests": 6},
}

CHAT = {
    "arrivals": "poisson",
    "rate_per_s": 10.0,
    "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 4, "max": 48},
    "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 4, "max": 40},
    "drain_s": 30,
}

BACKLOG = dict(CHAT, arrivals="backlog", backlog_per_s=10.0)


def config(**over):
    c = copy.deepcopy(CONFIG)
    c.update(over)
    return c


def cell(mix=CHAT, cfg=None):
    from bench.lib.spec import Cell, Metric

    e2e = [Metric("ttft_p90_ms", "ms"), Metric("tpot_p90_ms", "ms"), Metric("setup_s", "s")]
    layer = [Metric("queue_wait_ms.p50", "ms")]
    return Cell("tiny.test", "tiny-qwen3", "test", 1, cfg or config(), dict(mix), e2e, layer)
