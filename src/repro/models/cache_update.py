"""The KV-cache manager: a stage's stacked cache, written in place.

A stage (`models/transformer.py`) owns its cache.  It carries the stacked
leaves, shaped (*lead, B, S, ...) with `lead` the scanned layer axes,
through its layer scan and hands each layer a `LayerCache`: the stack and
the layer's place in it.  The layer computes the rows it adds (one per
batch row in decode, the prompt segment in prefill) and returns them
through `LayerCache.write`, which writes them into the carried stack and
nothing else of it; `LayerCache.read` then gives the layer its own
(B, S, ...) leaves, new rows included, to attend against.  With the cache
donated to the step, a decode step writes only the rows it adds.

How a write lands follows from what the code can observe:

  * a scalar index (aligned batches; every prefill): one
    `dynamic_update_slice` of the rows or segment at [*at, 0, index];
  * a (B,) index (continuous batching, each slot at its own length): one
    scatter of batch row b at [*at, b, index[b]];
  * a sequence axis split across devices (more than one device along the
    mesh axes in the spec's sequence slot; never without a mesh, as on one
    chip): a `dynamic_update_slice` at a traced sequence index has made
    XLA SPMD replicate-update-reshard, an all-gather of the entire cache
    per layer per step (observed: ~347 GB/device/token for llama3-405b
    decode).  There the layer's leaves are rewritten by a masked
    elementwise write, which partitions along every axis, and put back at
    the layer's place.

`insert_rows` is the slot insert of continuous batching.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .sharding import devices_along, shard


@dataclasses.dataclass(frozen=True)
class LayerCache:
    """One layer's place in its stage's stacked cache."""

    stack: Dict[str, jnp.ndarray]  # leaves (*lead, B, S, ...)
    at: Tuple[Any, ...]  # this layer's index on each lead axis

    def write(
        self,
        rows: Dict[str, jnp.ndarray],  # leaves (B, L, ...); L == 1 in decode
        index: jnp.ndarray,  # scalar int32, or (B,) int32 per-row positions
        *,
        spec: Tuple,  # logical spec of the layer's (B, S, ...) leaves
    ) -> "LayerCache":
        """Write `rows` at sequence position `index` (row b at `index[b]`
        for a vector) and return the handle on the updated stack."""
        split = devices_along(spec[1]) > 1
        index = jnp.asarray(index, jnp.int32)
        stack = dict(self.stack)
        for name, new in rows.items():
            leaf, new = stack[name], new.astype(stack[name].dtype)
            if split:
                stack[name] = _write_masked(leaf, new, self.at, index, spec)
            else:
                stack[name] = _write_in_place(leaf, new, self.at, index)
        return LayerCache(stack, self.at)

    def read(self) -> Dict[str, jnp.ndarray]:
        """The layer's own (B, S, ...) leaves."""
        return {name: leaf[self.at] for name, leaf in self.stack.items()}


def _write_in_place(leaf, new, at, index):
    if index.ndim == 0:
        start = (*at, 0, index) + (0,) * (leaf.ndim - len(at) - 2)
        return jax.lax.dynamic_update_slice(leaf, new[(None,) * len(at)], start)
    B, L = new.shape[:2]
    rows = jnp.arange(B)[:, None]
    cols = index[:, None] + jnp.arange(L)
    return leaf.at[(*at, rows, cols)].set(new, indices_are_sorted=True, unique_indices=True)


def _write_masked(leaf, new, at, index, spec):
    layer = leaf[at]
    S, L = layer.shape[1], new.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, S) + (1,) * (layer.ndim - 2), 1)
    start = index.reshape((-1,) + (1,) * (layer.ndim - 1))
    if L == S:
        layer = new  # the segment covers the buffer (standard prefill)
    elif L == 1:
        layer = jnp.where(pos == start, new, layer)
    else:
        # a shorter segment lands at its own positions, which holds for a
        # prefill at index 0, the only segment write the launchers lower
        seg = jnp.pad(new, ((0, 0), (0, S - L)) + ((0, 0),) * (layer.ndim - 2))
        layer = jnp.where((pos >= start) & (pos < start + L), seg, layer)
    layer = shard(layer, *spec)
    return jax.lax.dynamic_update_slice(leaf, layer[(None,) * len(at)], (*at,) + (0,) * layer.ndim)


def insert_rows(
    big: jnp.ndarray,
    small: jnp.ndarray,
    slots: jnp.ndarray,  # (n,) int32 indices into big's batch axis
    axis: int,
) -> jnp.ndarray:
    """Scatter `small`'s batch rows into `big` at `slots` along `axis`.

    The slot-insert primitive for continuous batching: a freshly prefilled
    n-request cache leaf replaces the corresponding rows of the persistent
    max_batch cache leaf, and nothing else of it is written.  Whole-row
    replacement — the previous occupant's KV is structurally unreachable,
    not merely masked."""
    return big.at[(slice(None),) * axis + (slots,)].set(small.astype(big.dtype))
