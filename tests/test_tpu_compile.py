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

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba2_ssd import ssd_pallas
from repro.kernels.mlstm_kernel import mlstm_pallas


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
