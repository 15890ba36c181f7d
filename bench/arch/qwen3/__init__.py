"""The binding of Qwen3 dense (`model_type` "qwen3"; `Qwen3ForCausalLM`):
grouped-query attention with per-head q/k RMSNorm, a SwiGLU MLP, tied or
untied head.  The interface is `bench/arch/__init__.py`'s."""

from .cost import (
    decode_attention_cost,
    decode_token_flops,
    flash_attention_cost,
    matmul_params,
    prefill_flops,
)
from .layout import model_config, to_program
from .reference import logits_at
from .weights import make_weights

KERNELS = ("decode_attention", "flash_attention")

TINY = {
    "config": {
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
    },
    "cases": {"untied": {}, "tied": {"tie_word_embeddings": True}},
    "atol": 0.15,
    "why": (
        "the engine holds activations, the KV cache and its matmul inputs in bf16 and "
        "rounds the head's output to bf16 before widening it (half an ulp is 0.008 at a "
        "logit of 4); measured at this size: largest error 0.065 over both requests and "
        "both head layouts.  0.15 leaves room for that and is far below an error of the "
        "mechanism (a wrong position, mask, norm or head gives errors of O(1) at logits "
        "of unit spread)"
    ),
}

__all__ = [
    "KERNELS",
    "TINY",
    "decode_attention_cost",
    "decode_token_flops",
    "flash_attention_cost",
    "logits_at",
    "make_weights",
    "matmul_params",
    "model_config",
    "prefill_flops",
    "to_program",
]
