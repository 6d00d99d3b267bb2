"""KV / recurrent-state cache containers.

Caches are plain dict pytrees of arrays (stacked over layers) so they flow
through jit/pjit with explicit shardings and can be declared abstractly for
the dry-run. Three attention cache styles:

  * full cache  — (L, B, S_max, KV, D); write cursor = ``length``
  * ring cache  — (L, B, W, KV, D) for sliding-window attention; slot
                  ``length % W``; per-slot absolute positions are stored so
                  masking stays position-based (see models.attention)
  * paged cache — (L, P, bs, KV, D): a global pool of P pages of ``bs``
                  tokens each, indirected through a per-slot ``block_tables``
                  row ((n_slots, MB) int32; -1 = unallocated). Slots own only
                  the pages their live tokens occupy, so KV memory scales
                  with tokens-in-use instead of n_slots × max_len. The pool
                  is token-major: a token's K (or V) is one (KV, D) row at
                  flat index page·bs + offset of its layer, the layout
                  attention reads, so a gathered block table needs no
                  transpose and the serving programs' layer scans scatter
                  into the carried pool in place (see models.transformer).

Recurrent families (xLSTM, RG-LRU) keep per-layer state tensors instead; see
their modules. ``length`` is a scalar int32 shared by all layers.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def full_cache_shape(
    n_layers: int, batch: int, max_len: int, kv_heads: int, head_dim: int,
    dtype=jnp.bfloat16,
) -> Dict[str, jax.ShapeDtypeStruct]:
    f = jax.ShapeDtypeStruct
    return {
        "k": f((n_layers, batch, max_len, kv_heads, head_dim), dtype),
        "v": f((n_layers, batch, max_len, kv_heads, head_dim), dtype),
        "length": f((batch,), jnp.int32),
    }


def full_cache_init(
    n_layers: int, batch: int, max_len: int, kv_heads: int, head_dim: int,
    dtype=jnp.bfloat16,
) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((n_layers, batch, max_len, kv_heads, head_dim), dtype),
        "v": jnp.zeros((n_layers, batch, max_len, kv_heads, head_dim), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def ring_cache_shape(
    n_layers: int, batch: int, window: int, kv_heads: int, head_dim: int,
    dtype=jnp.bfloat16,
) -> Dict[str, jax.ShapeDtypeStruct]:
    f = jax.ShapeDtypeStruct
    return {
        "k": f((n_layers, batch, window, kv_heads, head_dim), dtype),
        "v": f((n_layers, batch, window, kv_heads, head_dim), dtype),
        "pos": f((batch, window), jnp.int32),
        "length": f((batch,), jnp.int32),
    }


def ring_cache_init(
    n_layers: int, batch: int, window: int, kv_heads: int, head_dim: int,
    dtype=jnp.bfloat16,
) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((n_layers, batch, window, kv_heads, head_dim), dtype),
        "v": jnp.zeros((n_layers, batch, window, kv_heads, head_dim), dtype),
        "pos": jnp.full((batch, window), -1, jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


# --------------------------------------------------------------------------- #
# Per-layer write ops (used inside the layer scan; arrays are layer slices).  #
# --------------------------------------------------------------------------- #
def full_cache_write(
    k_layer: jax.Array,       # (B, S_max, KV, D)
    v_layer: jax.Array,
    k_new: jax.Array,         # (B, S_new, KV, D)
    v_new: jax.Array,
    start: jax.Array,         # scalar int32 — write offset
) -> Tuple[jax.Array, jax.Array]:
    k_layer = jax.lax.dynamic_update_slice(k_layer, k_new.astype(k_layer.dtype), (0, start, 0, 0))
    v_layer = jax.lax.dynamic_update_slice(v_layer, v_new.astype(v_layer.dtype), (0, start, 0, 0))
    return k_layer, v_layer


def full_cache_write_token(
    k_layer: jax.Array,       # (B, S_max, KV, D)
    v_layer: jax.Array,
    k_new: jax.Array,         # (B, 1, KV, D)
    v_new: jax.Array,
    positions: jax.Array,     # (B,) int32 — per-slot write positions
    active: Optional[jax.Array] = None,   # (B,) bool — rows allowed to write
) -> Tuple[jax.Array, jax.Array]:
    b, s_max = k_layer.shape[:2]
    rows = jnp.arange(b)
    if active is not None:
        # inactive rows write at S_max → dropped by the scatter (the fused
        # decode loop keeps finished slots as no-ops instead of early-exiting)
        positions = jnp.where(active, positions, s_max)
    k_layer = k_layer.at[rows, positions].set(
        k_new[:, 0].astype(k_layer.dtype), mode="drop"
    )
    v_layer = v_layer.at[rows, positions].set(
        v_new[:, 0].astype(v_layer.dtype), mode="drop"
    )
    return k_layer, v_layer


def ring_cache_write_token(
    k_layer: jax.Array,       # (B, W, KV, D)
    v_layer: jax.Array,
    k_new: jax.Array,         # (B, 1, KV, D)
    v_new: jax.Array,
    positions: jax.Array,     # (B,) int32 — absolute token positions
    active: Optional[jax.Array] = None,   # (B,) bool — rows allowed to write
) -> Tuple[jax.Array, jax.Array]:
    b, w = k_layer.shape[:2]
    rows = jnp.arange(b)
    slots = jnp.mod(positions, w)
    if active is not None:
        slots = jnp.where(active, slots, w)   # OOB → dropped
    k_layer = k_layer.at[rows, slots].set(
        k_new[:, 0].astype(k_layer.dtype), mode="drop"
    )
    v_layer = v_layer.at[rows, slots].set(
        v_new[:, 0].astype(v_layer.dtype), mode="drop"
    )
    return k_layer, v_layer


def ring_positions_write_token(
    pos: jax.Array, positions: jax.Array,
    active: Optional[jax.Array] = None,
) -> jax.Array:
    """Update the (B, W) slot→absolute-position map for one token per slot."""
    b, w = pos.shape
    rows = jnp.arange(b)
    slots = jnp.mod(positions, w)
    if active is not None:
        slots = jnp.where(active, slots, w)   # OOB → dropped
    return pos.at[rows, slots].set(positions.astype(pos.dtype), mode="drop")


def ring_cache_write_prefill(
    k_layer: jax.Array,       # (B, W, KV, D)
    v_layer: jax.Array,
    k_new: jax.Array,         # (B, S, KV, D) — token p at row p
    v_new: jax.Array,
    pos_map: Optional[jax.Array] = None,   # (B, W) slot→position (-1 empty)
) -> Tuple[jax.Array, jax.Array]:
    """Bulk write of a prefill into a ring cache.

    ``pos_map`` (from ``ring_positions_prefill``) names the absolute position
    each ring slot should hold — per batch row, so ragged prompts (engine
    path) fill correctly. Slots mapped to -1 are zeroed. With no map, a
    uniform full-width prefill is assumed."""
    w = k_layer.shape[1]
    s = k_new.shape[1]
    b = k_layer.shape[0]
    if pos_map is None:
        pos_map = ring_positions_prefill(b, w, s)
    rows = jnp.arange(b)[:, None]
    idx = jnp.clip(pos_map, 0, s - 1)
    valid = (pos_map >= 0)[..., None, None]
    k_layer = jnp.where(valid, k_new[rows, idx], 0).astype(k_layer.dtype)
    v_layer = jnp.where(valid, v_new[rows, idx], 0).astype(v_layer.dtype)
    return k_layer, v_layer


def paged_cache_shape(
    n_layers: int, num_pages: int, page_size: int, kv_heads: int,
    head_dim: int, n_slots: int, max_pages_per_slot: int,
    dtype=jnp.bfloat16,
) -> Dict[str, jax.ShapeDtypeStruct]:
    f = jax.ShapeDtypeStruct
    return {
        "k": f((n_layers, num_pages, page_size, kv_heads, head_dim), dtype),
        "v": f((n_layers, num_pages, page_size, kv_heads, head_dim), dtype),
        "block_tables": f((n_slots, max_pages_per_slot), jnp.int32),
        "length": f((n_slots,), jnp.int32),
    }


def paged_cache_init(
    n_layers: int, num_pages: int, page_size: int, kv_heads: int,
    head_dim: int, n_slots: int, max_pages_per_slot: int,
    dtype=jnp.bfloat16,
) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((n_layers, num_pages, page_size, kv_heads, head_dim), dtype),
        "v": jnp.zeros((n_layers, num_pages, page_size, kv_heads, head_dim), dtype),
        "block_tables": jnp.full((n_slots, max_pages_per_slot), -1, jnp.int32),
        "length": jnp.zeros((n_slots,), jnp.int32),
    }


def paged_cache_write(
    k_pool: jax.Array,        # (L, P, bs, KV, D) — the whole page pool
    v_pool: jax.Array,
    layer: jax.Array,         # scalar int32 — the layer written
    k_new: jax.Array,         # (B, S, KV, D) — token t of row b at position
    v_new: jax.Array,         #                 starts[b] + t
    block_tables: jax.Array,  # (B, MB) int32; -1 = unallocated
    starts: jax.Array,        # (B,) int32 — first token's absolute position
    lens: jax.Array,          # (B,) int32 — valid tokens per row (≤ S)
) -> Tuple[jax.Array, jax.Array]:
    """Scatter rows of tokens into ``layer`` of the page pool through the
    block table: each token is one (KV, D) row at flat index page·bs +
    offset. The pools come back whole, so a layer scan that carries them
    updates them in place.

    Rows may sit at different offsets (ragged chunked prefill); tokens beyond
    ``lens`` or mapping to an unallocated page are dropped, so padded batch
    rows can point at any table row without corrupting it.
    """
    n_layers, p, bs, kv, d = k_pool.shape
    b, s = k_new.shape[:2]
    mb = block_tables.shape[1]
    pos = starts[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]   # (B, S)
    blk = pos // bs
    page = jnp.take_along_axis(block_tables, jnp.clip(blk, 0, mb - 1), axis=1)
    valid = (jnp.arange(s)[None, :] < lens[:, None]) & (page >= 0) & (blk < mb)
    flat = jnp.where(valid, page * bs + pos % bs, p * bs).reshape(-1)  # OOB → drop

    def put(pool, new):
        rows = pool.reshape(n_layers, p * bs, kv, d).at[layer, flat].set(
            new.reshape(b * s, kv, d).astype(pool.dtype), mode="drop"
        )
        return rows.reshape(pool.shape)

    return put(k_pool, k_new), put(v_pool, v_new)


def paged_cache_write_token(
    k_pool: jax.Array,        # (L, P, bs, KV, D)
    v_pool: jax.Array,
    layer: jax.Array,         # scalar int32
    k_new: jax.Array,         # (B, 1, KV, D)
    v_new: jax.Array,
    block_tables: jax.Array,  # (B, MB)
    positions: jax.Array,     # (B,) int32 — absolute write positions
    active: jax.Array,        # (B,) bool — rows allowed to write
) -> Tuple[jax.Array, jax.Array]:
    """One-token-per-slot decode write. Unlike the dense cache (where idle
    rows absorb garbage harmlessly), paged pages are shared through the
    allocator, so inactive slots MUST NOT write — their row could alias a
    page now owned by another slot."""
    return paged_cache_write(
        k_pool, v_pool, layer, k_new, v_new, block_tables, positions,
        active.astype(jnp.int32),
    )


def paged_gather_kv(
    k_pool: jax.Array,        # (L, P, bs, KV, D)
    v_pool: jax.Array,
    layer: jax.Array,         # scalar int32 — the layer read
    block_tables: jax.Array,  # (B, MB)
) -> Tuple[jax.Array, jax.Array]:
    """Assemble each slot's logical KV sequence, (B, MB·bs, KV, D), from
    ``layer`` of the page pool — the pure-jnp realization of what the Pallas
    paged kernel does with block-table-indirected DMA. Unallocated pages
    read page 0; callers mask those positions via ``paged_key_positions``."""
    _, _, bs, kv, d = k_pool.shape
    b, mb = block_tables.shape
    pages = jnp.maximum(block_tables, 0)
    k_ctx = k_pool[layer, pages].reshape(b, mb * bs, kv, d)
    v_ctx = v_pool[layer, pages].reshape(b, mb * bs, kv, d)
    return k_ctx, v_ctx


def paged_key_positions(
    block_tables: jax.Array,  # (B, MB)
    lengths: jax.Array,       # (B,) — valid tokens per slot
    page_size: int,
) -> jax.Array:
    """(B, MB·bs) position map for gathered paged KV: index i where valid,
    -1 where past ``lengths`` or on an unallocated page (masked out by
    position-based attention, see models.attention)."""
    b, mb = block_tables.shape
    idx = jnp.arange(mb * page_size, dtype=jnp.int32)
    page = block_tables[:, idx // page_size]
    valid = (idx[None, :] < lengths[:, None]) & (page >= 0)
    return jnp.where(valid, idx[None, :], -1)


def ring_positions_prefill(batch: int, window: int, s) -> jax.Array:
    """Slot→position map after prefills of length ``s``.

    ``s`` may be a static int (uniform prefill) or a (B,) vector of
    per-slot lengths (engine path). Slot z holds the largest p < s with
    p ≡ z (mod w); slots beyond the fill level hold -1."""
    w = window
    slots = jnp.arange(w, dtype=jnp.int32)
    if isinstance(s, int):
        if s <= w:
            pos = jnp.where(slots < s, slots, -1)
        else:
            pos = s - 1 - jnp.mod((s - 1 - slots), w)
        return jnp.broadcast_to(pos[None, :], (batch, w)).astype(jnp.int32)
    sv = s.astype(jnp.int32)[:, None]                      # (B, 1)
    # largest p < s with p ≡ z (mod w); equals z itself when s <= w
    pos = sv - 1 - jnp.mod(sv - 1 - slots[None, :], w)     # (B, W)
    pos = jnp.where((slots[None, :] >= sv) & (sv <= w), -1, pos)
    return pos.astype(jnp.int32)
