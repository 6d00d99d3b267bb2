"""Decoder-only transformer LM — the workhorse for 7 of the 10 assigned
architectures (dense, MoE, VLM-backbone variants).

Design notes:
  * Layers are scan-stacked: one set of block weights with a leading
    "layers" dim, iterated with ``jax.lax.scan``. This keeps the lowered HLO
    O(1) in depth — essential for compiling 512-device dry-runs of 56-layer
    models on one CPU core, and it is how production JAX LMs ship anyway.
  * Blocks are optionally rematerialized (``jax.checkpoint``) for training.
  * Attention is the pure-jnp reference (``models.attention``); the Pallas
    kernels implement the same contract for TPU execution.
  * Serving splits into ``prefill`` (writes the KV cache, returns last-token
    logits) and ``decode_step`` (one token per active slot). Sliding-window
    configs use a ring cache of size W instead of the full context.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..distributed.sharding import constrain_kv_for_cache, constrain_residual
from .attention import attention, attention_any, attention_paged
from .cache import (
    full_cache_init,
    full_cache_shape,
    full_cache_write,
    full_cache_write_token,
    paged_cache_init,
    paged_cache_shape,
    paged_cache_write,
    paged_cache_write_token,
    ring_cache_init,
    ring_cache_shape,
    ring_cache_write_prefill,
    ring_cache_write_token,
    ring_positions_prefill,
    ring_positions_write_token,
)
from .layers import (
    ParamDef,
    apply_m_rope,
    apply_norm,
    apply_rope,
    cross_entropy_loss,
    embed_defs,
    embed_tokens,
    mlp_apply,
    mlp_defs,
    moe_aux_weight,
    norm_defs,
    rms_norm,
    unembed,
)
from .moe import moe_apply, moe_defs

Params = Dict[str, Any]


class TransformerLM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.hd = cfg.resolved_head_dim
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    # ------------------------------------------------------------------ #
    # Parameters                                                          #
    # ------------------------------------------------------------------ #
    def param_defs(self) -> Params:
        cfg, hd, dt = self.cfg, self.hd, self.dtype
        L = cfg.n_layers
        block: Params = {
            "norm_attn": norm_defs(cfg.d_model, cfg.norm_kind, dt, layers=L),
            "norm_mlp": norm_defs(cfg.d_model, cfg.norm_kind, dt, layers=L),
            "wq": ParamDef((L, cfg.d_model, cfg.n_heads, hd), ("layers", "embed", "heads", "head_dim"), dt),
            "wk": ParamDef((L, cfg.d_model, cfg.n_kv_heads, hd), ("layers", "embed", "kv_heads", "head_dim"), dt),
            "wv": ParamDef((L, cfg.d_model, cfg.n_kv_heads, hd), ("layers", "embed", "kv_heads", "head_dim"), dt),
            "wo": ParamDef((L, cfg.n_heads, hd, cfg.d_model), ("layers", "heads", "head_dim", "embed"), dt),
        }
        if cfg.use_bias:
            block["bq"] = ParamDef((L, cfg.n_heads, hd), ("layers", "heads", "head_dim"), dt, "zeros")
            block["bk"] = ParamDef((L, cfg.n_kv_heads, hd), ("layers", "kv_heads", "head_dim"), dt, "zeros")
            block["bv"] = ParamDef((L, cfg.n_kv_heads, hd), ("layers", "kv_heads", "head_dim"), dt, "zeros")
            block["bo"] = ParamDef((L, cfg.d_model), ("layers", "embed"), dt, "zeros")
        if cfg.qk_norm:
            block["q_norm"] = ParamDef((L, hd), ("layers", "head_dim"), dt, "ones")
            block["k_norm"] = ParamDef((L, hd), ("layers", "head_dim"), dt, "ones")
        if cfg.is_moe:
            block["moe"] = moe_defs(L, cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.mlp_kind, dt)
        else:
            block["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff, cfg.mlp_kind, dt, layers=L, use_bias=cfg.use_bias)
        return {
            "embed": embed_defs(cfg.vocab_size, cfg.d_model, dt, tie=cfg.tie_embeddings),
            "blocks": block,
            "norm_final": norm_defs(cfg.d_model, cfg.norm_kind, dt),
        }

    # ------------------------------------------------------------------ #
    # One transformer block (full-sequence form)                          #
    # ------------------------------------------------------------------ #
    def _block_full(
        self,
        h: jax.Array,                     # (B, S, D)
        lp: Params,                       # one layer's params (scan slice)
        positions: jax.Array,             # (B, S) or (B, S, 3)
        k_positions: jax.Array,           # (B, S)
    ) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, jax.Array]]:
        cfg = self.cfg
        x = apply_norm(h, lp["norm_attn"], cfg.norm_kind, cfg.norm_eps)
        q, k, v = self._qkv_block(x, lp)
        if cfg.m_rope:
            q = apply_m_rope(q, positions, cfg.m_rope_sections, cfg.rope_theta)
            k = apply_m_rope(k, positions, cfg.m_rope_sections, cfg.rope_theta)
            qpos = positions[..., 0]
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            qpos = positions
        attn_out = attention_any(
            q, k, v,
            q_positions=qpos,
            k_positions=k_positions,
            causal=True,
            window=cfg.sliding_window,
        )
        attn_out = jnp.einsum("bshk,hkd->bsd", attn_out, lp["wo"])
        if cfg.use_bias:
            attn_out = attn_out + lp["bo"]
        h = h + attn_out

        x = apply_norm(h, lp["norm_mlp"], cfg.norm_kind, cfg.norm_eps)
        if cfg.is_moe:
            mlp_out, aux = moe_apply(
                x, lp["moe"],
                n_experts=cfg.n_experts,
                top_k=cfg.experts_per_token,
                mlp_kind=cfg.mlp_kind,
                capacity_factor=cfg.moe_capacity_factor,
                group_size=cfg.moe_group_size,
            )
        else:
            mlp_out, aux = mlp_apply(x, lp["mlp"], cfg.mlp_kind), jnp.zeros((), jnp.float32)
        h = h + mlp_out
        return h, aux, (k, v)

    # ------------------------------------------------------------------ #
    # Training / full forward                                             #
    # ------------------------------------------------------------------ #
    def forward(
        self,
        params: Params,
        tokens: jax.Array,                 # (B, S) int32
        patch_embeds: Optional[jax.Array] = None,  # (B, P, D) VLM stub input
        remat: bool = True,
    ) -> Tuple[jax.Array, jax.Array]:
        """Full causal forward → (logits (B,S,V) f32, moe aux loss)."""
        cfg = self.cfg
        b, s = tokens.shape
        h = embed_tokens(tokens, params["embed"]).astype(self.dtype)
        if patch_embeds is not None and cfg.num_patch_tokens > 0:
            p = patch_embeds.shape[1]
            pad = jnp.zeros((b, s - p, cfg.d_model), patch_embeds.dtype)
            merged = jnp.concatenate([patch_embeds, pad], axis=1).astype(self.dtype)
            is_patch = (jnp.arange(s) < p)[None, :, None]
            h = jnp.where(is_patch, merged, h)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        if cfg.m_rope:
            positions = jnp.broadcast_to(positions[..., None], (b, s, 3))
        k_positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

        def body(carry, lp):
            h, aux = carry
            h, aux_l, _ = self._block_full(h, lp, positions, k_positions)
            h = constrain_residual(h)
            return (h, aux + aux_l), None

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable
            )
        (h, aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)), params["blocks"])
        h = apply_norm(h, params["norm_final"], cfg.norm_kind, cfg.norm_eps)
        logits = unembed(h, params["embed"])
        return logits.astype(jnp.float32), aux

    def loss(
        self,
        params: Params,
        batch: Dict[str, jax.Array],
        remat: bool = True,
    ) -> jax.Array:
        logits, aux = self.forward(
            params, batch["tokens"], batch.get("patch_embeds"), remat=remat
        )
        return cross_entropy_loss(logits, batch["labels"], batch.get("mask")) + (
            moe_aux_weight(self.cfg) * aux
        )

    # ------------------------------------------------------------------ #
    # Serving: cache declaration                                          #
    # ------------------------------------------------------------------ #
    def cache_shape(self, batch: int, max_len: int):
        cfg = self.cfg
        if cfg.sliding_window > 0:
            w = min(cfg.sliding_window, max_len)
            return ring_cache_shape(cfg.n_layers, batch, w, cfg.n_kv_heads, self.hd, self.dtype)
        return full_cache_shape(cfg.n_layers, batch, max_len, cfg.n_kv_heads, self.hd, self.dtype)

    def cache_init(self, batch: int, max_len: int):
        cfg = self.cfg
        if cfg.sliding_window > 0:
            w = min(cfg.sliding_window, max_len)
            return ring_cache_init(cfg.n_layers, batch, w, cfg.n_kv_heads, self.hd, self.dtype)
        return full_cache_init(cfg.n_layers, batch, max_len, cfg.n_kv_heads, self.hd, self.dtype)

    # ------------------------------------------------------------------ #
    # Serving: paged cache (block-table layout; see models.cache)         #
    # ------------------------------------------------------------------ #
    def _check_paged_supported(self) -> None:
        if self.cfg.sliding_window > 0:
            raise NotImplementedError(
                "paged KV cache does not support sliding-window configs "
                "(the ring cache already bounds their KV memory at W)"
            )

    def paged_cache_shape(
        self, num_pages: int, page_size: int, n_slots: int,
        max_pages_per_slot: int,
    ):
        self._check_paged_supported()
        return paged_cache_shape(
            self.cfg.n_layers, num_pages, page_size, self.cfg.n_kv_heads,
            self.hd, n_slots, max_pages_per_slot, self.dtype,
        )

    def paged_cache_init(
        self, num_pages: int, page_size: int, n_slots: int,
        max_pages_per_slot: int,
    ):
        self._check_paged_supported()
        return paged_cache_init(
            self.cfg.n_layers, num_pages, page_size, self.cfg.n_kv_heads,
            self.hd, n_slots, max_pages_per_slot, self.dtype,
        )

    def _paged_layer_scan(self, body, h, params: Params, cache):
        """Run ``body((h, k_pool, v_pool), (layer_params, layer_index))``
        over the layers with the whole paged pool in the carry: each layer
        scatters its new K/V into the pool at its own index and reads it
        back there, so the donated pool is updated in place rather than
        rebuilt as a stacked scan output. Returns (h, k_pool, v_pool)."""
        layers = jnp.arange(self.cfg.n_layers, dtype=jnp.int32)
        (h, k_pool, v_pool), _ = jax.lax.scan(
            body, (h, cache["k"], cache["v"]), (params["blocks"], layers)
        )
        return h, k_pool, v_pool

    # ------------------------------------------------------------------ #
    # Serving: prefill                                                    #
    # ------------------------------------------------------------------ #
    def prefill(
        self,
        params: Params,
        tokens: jax.Array,                 # (B, S) int32, right-padded
        cache: Dict[str, jax.Array],
        patch_embeds: Optional[jax.Array] = None,
        lengths: Optional[jax.Array] = None,   # (B,) true prompt lengths
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Process the (right-padded) prompts, fill the cache, and return the
        logits at each prompt's last real token. ``lengths`` defaults to the
        full padded width (uniform prefill — the dry-run cells)."""
        cfg = self.cfg
        b, s = tokens.shape
        h = embed_tokens(tokens, params["embed"]).astype(self.dtype)
        if patch_embeds is not None and cfg.num_patch_tokens > 0:
            p = patch_embeds.shape[1]
            pad = jnp.zeros((b, s - p, cfg.d_model), patch_embeds.dtype)
            merged = jnp.concatenate([patch_embeds, pad], axis=1).astype(self.dtype)
            h = jnp.where((jnp.arange(s) < p)[None, :, None], merged, h)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        if cfg.m_rope:
            pos_in = jnp.broadcast_to(positions[..., None], (b, s, 3))
        else:
            pos_in = positions

        ring = cfg.sliding_window > 0
        ring_pos_map = None
        if ring:
            w = cache["k"].shape[2]
            ring_pos_map = ring_positions_prefill(
                b, w, s if lengths is None else lengths.astype(jnp.int32)
            )

        def body(carry, xs):
            h = carry
            lp, kc, vc = xs
            h, _, (k_new, v_new) = self._block_full(h, lp, pos_in, positions)
            h = constrain_residual(h)
            if not ring:
                # full-cache writes must match the cache's CP (seq-sharded)
                # layout; ring caches use a gather-write where the constraint
                # back-fires (measured +60% collectives for mixtral prefill)
                k_new = constrain_kv_for_cache(k_new, cfg.n_kv_heads)
                v_new = constrain_kv_for_cache(v_new, cfg.n_kv_heads)
            if ring:
                kc, vc = ring_cache_write_prefill(kc, vc, k_new, v_new, ring_pos_map)
            else:
                kc, vc = full_cache_write(kc, vc, k_new, v_new, jnp.int32(0))
            return h, (kc, vc)

        h, (k_all, v_all) = jax.lax.scan(
            body, h, (params["blocks"], cache["k"], cache["v"])
        )
        h = apply_norm(h, params["norm_final"], cfg.norm_kind, cfg.norm_eps)
        if lengths is None:
            h_last = h[:, -1, :]
            len_vec = jnp.full((b,), s, jnp.int32)
        else:
            len_vec = lengths.astype(jnp.int32)
            h_last = h[jnp.arange(b), jnp.maximum(len_vec - 1, 0), :]
        logits = unembed(h_last, params["embed"]).astype(jnp.float32)
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = k_all, v_all
        new_cache["length"] = len_vec
        if ring:
            new_cache["pos"] = ring_pos_map
        return logits, new_cache

    # ------------------------------------------------------------------ #
    # Serving: chunked prefill into a paged cache                         #
    # ------------------------------------------------------------------ #
    def prefill_chunk(
        self,
        params: Params,
        tokens: jax.Array,                 # (B, C) int32 — one chunk per row
        cache: Dict[str, jax.Array],       # paged cache (the whole pool)
        slot_ids: jax.Array,               # (B,) int32; >= n_slots → pad row
        starts: jax.Array,                 # (B,) int32 — chunk offset in prompt
        chunk_lens: jax.Array,             # (B,) int32 — real tokens (≤ C)
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Process one prompt chunk per row, writing K/V straight into the
        rows' paged blocks (no throwaway cache, no padded full-row scatter).
        Queries attend to everything the slot has accumulated — earlier
        chunks live in the same pages. Returns the logits at each row's last
        real chunk token (only meaningful for a prompt's final chunk) and the
        updated pool."""
        cfg = self.cfg
        self._check_paged_supported()
        b, c = tokens.shape
        n_slots = cache["block_tables"].shape[0]
        h = embed_tokens(tokens, params["embed"]).astype(self.dtype)
        positions = starts[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        pos_in = (
            jnp.broadcast_to(positions[..., None], (b, c, 3))
            if cfg.m_rope else positions
        )
        tables = cache["block_tables"][jnp.clip(slot_ids, 0, n_slots - 1)]
        new_lens = starts + chunk_lens

        def body(carry, xs):
            h, kp, vp = carry
            lp, i = xs
            x = apply_norm(h, lp["norm_attn"], cfg.norm_kind, cfg.norm_eps)
            q, k, v = self._qkv_block(x, lp)
            if cfg.m_rope:
                q = apply_m_rope(q, pos_in, cfg.m_rope_sections, cfg.rope_theta)
                k = apply_m_rope(k, pos_in, cfg.m_rope_sections, cfg.rope_theta)
            else:
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
            kp, vp = paged_cache_write(kp, vp, i, k, v, tables, starts, chunk_lens)
            attn_out = attention_paged(
                q, kp, vp, i, tables,
                q_positions=positions,
                valid_lengths=new_lens,
                causal=True,
            )
            attn_out = jnp.einsum("bshk,hkd->bsd", attn_out, lp["wo"])
            if cfg.use_bias:
                attn_out = attn_out + lp["bo"]
            h = self._mlp_block(h + attn_out, lp)
            return (h, kp, vp), None

        h, k_all, v_all = self._paged_layer_scan(body, h, params, cache)
        h = apply_norm(h, params["norm_final"], cfg.norm_kind, cfg.norm_eps)
        h_last = h[jnp.arange(b), jnp.maximum(chunk_lens - 1, 0)]
        logits = unembed(h_last, params["embed"]).astype(jnp.float32)
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = k_all, v_all
        new_cache["length"] = cache["length"].at[slot_ids].set(
            new_lens, mode="drop"
        )
        return logits, new_cache

    # ------------------------------------------------------------------ #
    # Serving: unified mixed prefill+decode dispatch (paged layout)       #
    # ------------------------------------------------------------------ #
    def _qkv_block(self, x, lp):
        """Shared q/k/v projection + qk-norm for the serving bodies."""
        cfg = self.cfg
        q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
        if cfg.use_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        return q, k, v

    def _mlp_block(self, h, lp):
        cfg = self.cfg
        x = apply_norm(h, lp["norm_mlp"], cfg.norm_kind, cfg.norm_eps)
        if cfg.is_moe:
            mlp_out, _ = moe_apply(
                x, lp["moe"],
                n_experts=cfg.n_experts,
                top_k=cfg.experts_per_token,
                mlp_kind=cfg.mlp_kind,
                capacity_factor=cfg.moe_capacity_factor,
                group_size=cfg.moe_group_size,
            )
        else:
            mlp_out = mlp_apply(x, lp["mlp"], cfg.mlp_kind)
        return h + mlp_out

    def mixed_step(
        self,
        params: Params,
        dec_tokens: jax.Array,             # (J,) int32 — pending token/slot
        cache: Dict[str, jax.Array],       # paged cache (the whole pool)
        chunk_tokens: jax.Array,           # (R, C) int32 — one chunk per row
        chunk_slots: jax.Array,            # (R,) int32; >= n_slots → pad row
        chunk_starts: jax.Array,           # (R,) int32 — offset in prompt
        chunk_lens: jax.Array,             # (R,) int32 — real tokens (≤ C)
        *,
        sampler,                           # serving.sampler.Sampler object
        dec_active: jax.Array,             # (J,) bool — slots decoding now
        rids: jax.Array,                   # (J+R,) int32 — request ids
        token_idx: jax.Array,              # (J+R,) int32 — sampled token index
        sample_rows: jax.Array,            # (J+R,) bool — rows that sample
        base_key: Optional[jax.Array] = None,  # typed PRNG key (stochastic)
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Process one *mixed* batch — a decode round over all J slots plus
        R ragged prefill-chunk rows — in ONE device dispatch over the paged
        KV pool.

        The two sub-batches keep their native shapes and run through a
        single layer scan: the chunk rows use exactly ``prefill_chunk``'s
        row-form page writes and chunk attention, the decode lanes exactly
        the paged ``decode_step`` math with ``dec_active`` masking. A mid-
        prefill slot is never bound, so the sub-batches touch disjoint
        slots and the mixed round is mathematically the sequential
        chunk-round-then-decode-round computation fused into one dispatch
        — prefill stops preempting decode because there is no separate
        prefill stage left to preempt it with.

        Sampling happens on device for every row flagged in ``sample_rows``
        (decode lanes first, then chunk rows: a prompt's final chunk emits
        its first output token in the same call), with per-row keys folded
        from ``(base_key, rid, token_idx)`` so streams stay a pure function
        of (seed, rid, token index) regardless of batch composition.
        Returns ``(sampled (J+R,) int32 with -1 on non-sampling rows,
        cache)``.
        """
        cfg = self.cfg
        self._check_paged_supported()
        j = dec_tokens.shape[0]
        r, c = chunk_tokens.shape
        n_slots = cache["block_tables"].shape[0]
        lengths = cache["length"]
        grow = dec_active.astype(jnp.int32)

        # decode-lane geometry (paged decode_step)
        dec_pos = lengths[:, None].astype(jnp.int32)            # (J, 1)
        dec_pos_in = (
            jnp.broadcast_to(dec_pos[..., None], (j, 1, 3))
            if cfg.m_rope else dec_pos
        )
        dec_tables = cache["block_tables"]

        # chunk-row geometry (prefill_chunk)
        ch_pos = chunk_starts[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        ch_pos_in = (
            jnp.broadcast_to(ch_pos[..., None], (r, c, 3))
            if cfg.m_rope else ch_pos
        )
        ch_tables = cache["block_tables"][jnp.clip(chunk_slots, 0, n_slots - 1)]
        ch_new_lens = chunk_starts + chunk_lens

        def body(carry, xs):
            (h_d, h_c), kp, vp = carry
            lp, i = xs
            # projections for both sub-batches
            x_d = apply_norm(h_d, lp["norm_attn"], cfg.norm_kind, cfg.norm_eps)
            q_d, k_d, v_d = self._qkv_block(x_d, lp)
            x_c = apply_norm(h_c, lp["norm_attn"], cfg.norm_kind, cfg.norm_eps)
            q_c, k_c, v_c = self._qkv_block(x_c, lp)
            if cfg.m_rope:
                q_d = apply_m_rope(q_d, dec_pos_in, cfg.m_rope_sections, cfg.rope_theta)
                k_d = apply_m_rope(k_d, dec_pos_in, cfg.m_rope_sections, cfg.rope_theta)
                q_c = apply_m_rope(q_c, ch_pos_in, cfg.m_rope_sections, cfg.rope_theta)
                k_c = apply_m_rope(k_c, ch_pos_in, cfg.m_rope_sections, cfg.rope_theta)
            else:
                q_d = apply_rope(q_d, dec_pos, cfg.rope_theta)
                k_d = apply_rope(k_d, dec_pos, cfg.rope_theta)
                q_c = apply_rope(q_c, ch_pos, cfg.rope_theta)
                k_c = apply_rope(k_c, ch_pos, cfg.rope_theta)
            # all page writes land before either attention reads — the
            # sub-batches own disjoint slots, so write order is irrelevant
            kp, vp = paged_cache_write(
                kp, vp, i, k_c, v_c, ch_tables, chunk_starts, chunk_lens
            )
            kp, vp = paged_cache_write_token(
                kp, vp, i, k_d, v_d, dec_tables, lengths, dec_active
            )
            attn_c = attention_paged(
                q_c, kp, vp, i, ch_tables,
                q_positions=ch_pos, valid_lengths=ch_new_lens, causal=True,
            )
            attn_d = attention_paged(
                q_d, kp, vp, i, dec_tables,
                q_positions=dec_pos, valid_lengths=lengths + grow, causal=True,
            )
            attn_d = jnp.einsum("bshk,hkd->bsd", attn_d, lp["wo"])
            attn_c = jnp.einsum("bshk,hkd->bsd", attn_c, lp["wo"])
            if cfg.use_bias:
                attn_d = attn_d + lp["bo"]
                attn_c = attn_c + lp["bo"]
            h_d = self._mlp_block(h_d + attn_d, lp)
            h_c = self._mlp_block(h_c + attn_c, lp)
            return ((h_d, h_c), kp, vp), None

        h_d = embed_tokens(dec_tokens[:, None], params["embed"]).astype(self.dtype)
        h_c = embed_tokens(chunk_tokens, params["embed"]).astype(self.dtype)
        (h_d, h_c), k_all, v_all = self._paged_layer_scan(
            body, (h_d, h_c), params, cache
        )
        h_d = apply_norm(h_d, params["norm_final"], cfg.norm_kind, cfg.norm_eps)
        h_c = apply_norm(h_c, params["norm_final"], cfg.norm_kind, cfg.norm_eps)
        h_last = jnp.concatenate(
            [h_d[:, 0], h_c[jnp.arange(r), jnp.maximum(chunk_lens - 1, 0)]]
        )
        logits = unembed(h_last, params["embed"]).astype(jnp.float32)
        if base_key is None:
            nxt = sampler(logits)
        else:
            from ..serving.sampler import fold_row_keys

            keys = fold_row_keys(base_key, rids, token_idx)
            nxt = sampler(logits, keys)
        sampled = jnp.where(sample_rows, nxt, -1)
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = k_all, v_all
        # decode lanes grow their slot by one; chunk rows set start+len
        # (disjoint slots; pad rows scatter out of range and drop)
        new_cache["length"] = (lengths + grow).at[chunk_slots].set(
            ch_new_lens, mode="drop"
        )
        return sampled, new_cache

    # ------------------------------------------------------------------ #
    # Serving: one decode step                                            #
    # ------------------------------------------------------------------ #
    def decode_step(
        self,
        params: Params,
        tokens: jax.Array,                 # (B,) int32 — last sampled token
        cache: Dict[str, jax.Array],
        active: Optional[jax.Array] = None,   # (B,) bool
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Append one token per slot; returns (logits (B,V) f32, cache).

        The cache layout is detected from the pytree: a ``block_tables`` key
        selects the paged path. ``active`` masks which slots may write and
        advance — mandatory for paged caches (an idle slot's stale block
        table could alias pages now owned by another slot) and load-bearing
        inside the fused multi-step loop, where a slot that hit its stop
        condition mid-horizon must become a no-op (no KV write, no length
        growth) instead of forcing the whole batch to exit. ``active=None``
        keeps the legacy all-slots-advance dense behavior."""
        if "block_tables" in cache:
            return self._decode_step_paged(params, tokens, cache, active)
        cfg = self.cfg
        b = tokens.shape[0]
        lengths = cache["length"]                     # (B,) per-slot lengths
        grow = (
            jnp.ones((b,), jnp.int32) if active is None
            else active.astype(jnp.int32)
        )
        h = embed_tokens(tokens[:, None], params["embed"]).astype(self.dtype)  # (B,1,D)
        positions = lengths[:, None].astype(jnp.int32)            # (B, 1)
        if cfg.m_rope:
            pos_in = jnp.broadcast_to(positions[..., None], (b, 1, 3))
        else:
            pos_in = positions

        ring = cfg.sliding_window > 0
        # Post-write key positions (same for every layer): each active slot's
        # new token sits at its own ``lengths[b]``; masked slots gain nothing.
        if ring:
            k_pos_now = ring_positions_write_token(cache["pos"], lengths, active)
        else:
            max_len = cache["k"].shape[2]
            idx = jnp.arange(max_len, dtype=jnp.int32)
            k_pos_now = jnp.where(
                idx[None, :] < (lengths + grow)[:, None], idx[None, :], -1
            )

        def body(h, xs):
            lp, kc, vc = xs
            x = apply_norm(h, lp["norm_attn"], cfg.norm_kind, cfg.norm_eps)
            q, k, v = self._qkv_block(x, lp)
            if cfg.m_rope:
                q = apply_m_rope(q, pos_in, cfg.m_rope_sections, cfg.rope_theta)
                k = apply_m_rope(k, pos_in, cfg.m_rope_sections, cfg.rope_theta)
            else:
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
            if ring:
                kc, vc = ring_cache_write_token(kc, vc, k, v, lengths, active)
            else:
                kc, vc = full_cache_write_token(kc, vc, k, v, lengths, active)
            attn_out = attention(
                q, kc, vc,
                q_positions=positions,
                k_positions=k_pos_now,
                causal=True,
                window=cfg.sliding_window,
            )
            attn_out = jnp.einsum("bshk,hkd->bsd", attn_out, lp["wo"])
            if cfg.use_bias:
                attn_out = attn_out + lp["bo"]
            h = self._mlp_block(h + attn_out, lp)
            return h, (kc, vc)

        h, (k_all, v_all) = jax.lax.scan(
            body, h, (params["blocks"], cache["k"], cache["v"])
        )
        h = apply_norm(h, params["norm_final"], cfg.norm_kind, cfg.norm_eps)
        logits = unembed(h[:, 0, :], params["embed"]).astype(jnp.float32)
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = k_all, v_all
        new_cache["length"] = lengths + grow
        if ring:
            new_cache["pos"] = k_pos_now
        return logits, new_cache

    def _decode_step_paged(
        self,
        params: Params,
        tokens: jax.Array,                 # (B,) int32
        cache: Dict[str, jax.Array],       # paged cache; B = n_slots
        active: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        b = tokens.shape[0]
        tables = cache["block_tables"]
        lengths = cache["length"]
        if active is None:
            active = jnp.ones((b,), jnp.bool_)
        grow = active.astype(jnp.int32)
        positions = lengths[:, None].astype(jnp.int32)
        if cfg.m_rope:
            pos_in = jnp.broadcast_to(positions[..., None], (b, 1, 3))
        else:
            pos_in = positions

        def body(carry, xs):
            h, kp, vp = carry
            lp, i = xs
            x = apply_norm(h, lp["norm_attn"], cfg.norm_kind, cfg.norm_eps)
            q, k, v = self._qkv_block(x, lp)
            if cfg.m_rope:
                q = apply_m_rope(q, pos_in, cfg.m_rope_sections, cfg.rope_theta)
                k = apply_m_rope(k, pos_in, cfg.m_rope_sections, cfg.rope_theta)
            else:
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
            kp, vp = paged_cache_write_token(kp, vp, i, k, v, tables, lengths, active)
            # post-write valid counts: active slots gained one token at
            # position ``lengths``; inactive slots' outputs are ignored
            attn_out = attention_paged(
                q, kp, vp, i, tables,
                q_positions=positions,
                valid_lengths=lengths + grow,
                causal=True,
            )
            attn_out = jnp.einsum("bshk,hkd->bsd", attn_out, lp["wo"])
            if cfg.use_bias:
                attn_out = attn_out + lp["bo"]
            h = self._mlp_block(h + attn_out, lp)
            return (h, kp, vp), None

        h = embed_tokens(tokens[:, None], params["embed"]).astype(self.dtype)
        h, k_all, v_all = self._paged_layer_scan(body, h, params, cache)
        h = apply_norm(h, params["norm_final"], cfg.norm_kind, cfg.norm_eps)
        logits = unembed(h[:, 0, :], params["embed"]).astype(jnp.float32)
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = k_all, v_all
        new_cache["length"] = lengths + grow
        return logits, new_cache

    # ------------------------------------------------------------------ #
    # Serving: fused multi-step decode                                    #
    # ------------------------------------------------------------------ #
    def decode_steps(
        self,
        params: Params,
        tokens: jax.Array,                 # (B,) int32 — last sampled token
        cache: Dict[str, jax.Array],       # dense or paged layout
        *,
        num_steps: int,                    # static — the fused horizon K
        sampler,                           # serving.sampler.Sampler object
        active: jax.Array,                 # (B,) bool — slots decoding now
        budgets: jax.Array,                # (B,) int32 — max tokens to emit
        rids: jax.Array,                   # (B,) int32 — request ids
        token_idx0: jax.Array,             # (B,) int32 — next token's index
        base_key: Optional[jax.Array] = None,  # typed PRNG key (stochastic)
        eos_id: Optional[int] = None,      # static
    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        """Run K decode iterations in ONE device dispatch: attention, KV
        append, and token sampling all stay on device; the host sees nothing
        until the horizon boundary. Works for both cache layouts (dispatch on
        ``block_tables`` happens inside ``decode_step``).

        Each iteration feeds the previous iteration's sampled token back in.
        A slot stops when it has emitted ``budgets[b]`` tokens or samples
        ``eos_id``; from then on it is a no-op (masked KV write, frozen
        length) rather than an early exit, so one finished slot never stalls
        the rest of the batch. Stochastic samplers draw per-row keys folded
        from ``(base_key, rid, token index)`` — a request's stream is
        invariant to the horizon K, the slot it occupies, and its batch
        neighbours, which is what makes fused and per-token decode exactly
        token-identical.

        Returns ``(token_block (K, B) int32 with -1 where a slot emitted
        nothing that iteration, emitted (B,) int32, active_out (B,) bool,
        last_token (B,) int32, cache)``.
        """
        from ..serving.sampler import fold_row_keys

        def body(carry, _):
            cur, act, counts, cache = carry
            logits, cache = self.decode_step(params, cur, cache, active=act)
            if base_key is None:
                nxt = sampler(logits)
            else:
                keys = fold_row_keys(base_key, rids, token_idx0 + counts)
                nxt = sampler(logits, keys)
            nxt = jnp.where(act, nxt, cur)          # frozen slots keep theirs
            counts = counts + act.astype(jnp.int32)
            new_act = act & (counts < budgets)
            if eos_id is not None:
                new_act = new_act & (nxt != eos_id)
            emitted_tok = jnp.where(act, nxt, -1)
            return (nxt, new_act, counts, cache), emitted_tok

        b = tokens.shape[0]
        carry0 = (tokens, active, jnp.zeros((b,), jnp.int32), cache)
        (last_tok, active_out, emitted, cache), token_block = jax.lax.scan(
            body, carry0, None, length=num_steps
        )
        return token_block, emitted, active_out, last_tok, cache
