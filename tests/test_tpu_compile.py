"""The Pallas kernels compile for a TPU v5e chip at real model widths.

Interpret mode (tests/test_kernels.py) checks numerics but not what Mosaic
accepts: block tiling, SMEM/VMEM placement and the primitives it can lower.
These tests compile each kernel for one chip of a *described* v5e:2x2
topology — the TPU compiler runs here without a chip attached — and assert
the Mosaic custom call is in the compiled HLO.  Nothing executes.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may hold the TPU library, and every pytest-xdist
worker imports this file, so an import-time call would give the workers
different test sets.
"""

import math
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba2_ssd import ssd_pallas
from repro.kernels.mlstm_kernel import mlstm_pallas
from repro.models.cache_update import LayerCache


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


# llama3-8b: H=32, K=8, head_dim=128.  (B, S) = a 2k-context decode batch
# and the served engine's (max_batch, max_len).
@pytest.mark.parametrize("B,S", [(8, 2048), (4, 1024)])
def test_decode_attention_compiles_llama3_8b(one_chip, B, S):
    H, K, D = 32, 8, 128
    txt = _compiled_text(
        partial(decode_attention_pallas, interpret=False), one_chip,
        ((B, H, D), BF16), ((B, S, K, D), BF16), ((B, S, K, D), BF16), ((B,), I32),
    )
    assert "tpu_custom_call" in txt


# Every other caller of the decode kernel at its real heads, head_dim,
# window and softcap (`repro.configs`): the stored-layout blocks
# (block_k, K, D) and the per-head `k_ref[:, h, :]` loads must lower at
# each K, D and group size.  S passes gemma2's window, so its first live
# block is past block 0.
@pytest.mark.parametrize("H,K,D,window,cap,S", [
    (32, 16, 128, 4096, 50.0, 8192),  # gemma2-27b local layer
    (32, 16, 128, None, 50.0, 8192),  # gemma2-27b global layer
    (16, 16, 128, None, None, 4096),  # olmoe-1b-7b (MHA)
    (32, 32, 64, None, None, 4096),  # zamba2-1.2b shared attention (MHA)
    (20, 20, 64, None, None, 448),  # whisper-large-v3 decoder (MHA)
    (14, 2, 64, None, None, 4096),  # internvl2-1b (group 7)
], ids=["gemma2-local", "gemma2-global", "olmoe", "zamba2", "whisper", "internvl2"])
def test_decode_attention_compiles_other_callers(one_chip, H, K, D, window, cap, S):
    B = 8
    txt = _compiled_text(
        partial(decode_attention_pallas, window=window, logit_cap=cap, interpret=False),
        one_chip,
        ((B, H, D), BF16), ((B, S, K, D), BF16), ((B, S, K, D), BF16), ((B,), I32),
    )
    assert "tpu_custom_call" in txt


def _cache_sized_copies(hlo: str, n: int):
    """Instructions (fused bodies included) that copy, transpose or reshape
    an array of exactly `n` elements: a pass over a whole layer's cache."""
    found = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* (copy|transpose|reshape)\(", hlo):
        if math.prod(int(d) for d in m.group(1).split(",") if d) == n:
            found.append(m.group(0))
    return found


# The benchmark's cells: (layers, max_batch, H) at S 2560, K 8, head_dim 128.
# The kernel is called as the model calls it: inside the layer scan, on the
# layer's leaves of the stacked cache after this step's row was written.
@pytest.mark.parametrize("L,B,H", [(28, 18, 16), (8, 22, 64)], ids=["qwen3-1.7b", "qwen3-32b"])
def test_decode_attention_reads_stored_layout_in_layer_scan(one_chip, L, B, H):
    S, K, D = 2560, 8, 128

    def step(q, k_new, v_new, stack, clen):
        def layer(carry, i):
            stack, x = carry
            cache = LayerCache(stack, (i,)).write(
                {"k": k_new, "v": v_new}, clen, spec=(None,) * 4
            )
            kv = cache.read()
            out = decode_attention_pallas(x, kv["k"], kv["v"], clen + 1, interpret=False)
            return (cache.stack, out), None

        (stack, x), _ = jax.lax.scan(layer, (stack, q), jnp.arange(L))
        return x, stack

    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    row = shape((B, 1, K, D), BF16)
    stack = {name: shape((L, B, S, K, D), BF16) for name in ("k", "v")}
    lowered = jax.jit(step, donate_argnums=3).lower(
        shape((B, H, D), BF16), row, row, stack, shape((B,), I32)
    )
    txt = lowered.compile().as_text()
    assert "tpu_custom_call" in txt
    assert _cache_sized_copies(txt, B * S * K * D) == []


# llama3-8b prefill; 500 is not a block multiple (padded and masked path),
# 100 is shorter than one block (the block is the whole unaligned length)
@pytest.mark.parametrize("S", [2048, 500, 100])
def test_flash_attention_compiles_llama3_8b(one_chip, S):
    H, K, D = 32, 8, 128
    txt = _compiled_text(
        partial(flash_attention_pallas, causal=True, interpret=False), one_chip,
        ((1, S, H, D), BF16), ((1, S, K, D), BF16), ((1, S, K, D), BF16),
    )
    assert "tpu_custom_call" in txt


def test_ssd_compiles_zamba2_1p2b(one_chip):
    # zamba2-1.2b: d_inner 4096 = 64 heads x P 64, G=2 groups, N=64 state
    B, S, H, P, G, N = 1, 2048, 64, 64, 2, 64
    txt = _compiled_text(
        partial(ssd_pallas, chunk=128, interpret=False), one_chip,
        ((B, S, H, P), BF16), ((B, S, H), F32), ((H,), F32),
        ((B, S, G, N), BF16), ((B, S, G, N), BF16), ((H,), F32),
    )
    assert "tpu_custom_call" in txt


def test_mlstm_compiles_xlstm_1p3b(one_chip):
    # xlstm-1.3b: 4 heads over the 2x up-projected 4096 -> head dim 1024
    B, S, H, D = 1, 2048, 4, 1024
    txt = _compiled_text(
        partial(mlstm_pallas, interpret=False), one_chip,
        ((B, S, H, D), BF16), ((B, S, H, D), BF16), ((B, S, H, D), BF16),
        ((B, S, H), F32), ((B, S, H), F32),
    )
    assert "tpu_custom_call" in txt
