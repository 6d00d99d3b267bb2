"""Reference (pure-jnp) attention with GQA, causal, sliding-window and
cross-attention masks.

This is the path the dry-run lowers (einsum attention partitions cleanly
under GSPMD). The Pallas kernels in ``repro.kernels`` implement the same
contracts for TPU execution and are validated against these functions.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def attention(
    q: jax.Array,                     # (B, Sq, H, D)
    k: jax.Array,                     # (B, Sk, KV, D)
    v: jax.Array,                     # (B, Sk, KV, D)
    *,
    q_positions: jax.Array,           # (B, Sq) int32
    k_positions: jax.Array,           # (B, Sk) int32; -1 = invalid slot
    causal: bool = True,
    window: int = 0,                  # 0 = unbounded
    scale: Optional[float] = None,
) -> jax.Array:
    """Masked multi-head attention with grouped KV heads.

    Masking is position-based so the same code serves packed prefill,
    ring-buffer (sliding-window) decode and full-cache decode:
      * invalid:   k_pos < 0
      * causal:    k_pos > q_pos
      * window:    q_pos - k_pos >= window (when window > 0)
    """
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    if h % kv != 0:
        raise ValueError(f"q heads {h} not divisible by kv heads {kv}")
    g = h // kv
    scale = scale if scale is not None else d ** -0.5

    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, kv, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # scores: (B, KV, G, Sq, Sk)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf)

    qpos = q_positions[:, None, None, :, None]          # (B,1,1,Sq,1)
    kpos = k_positions[:, None, None, None, :]          # (B,1,1,1,Sk)
    mask = kpos >= 0
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window > 0:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vf)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def chunked_attention(
    q: jax.Array,                     # (B, Sq, H, D)
    k: jax.Array,                     # (B, Sk, KV, D)
    v: jax.Array,                     # (B, Sk, KV, D)
    *,
    q_positions: jax.Array,           # (B, Sq) int32
    k_positions: jax.Array,           # (B, Sk) int32; -1 = invalid
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    q_chunk: int = 512,
    k_chunk: int = 1024,
) -> jax.Array:
    """Flash-style attention in pure jnp: nested scans over query/key blocks
    with a running (max, sum, acc) online softmax. Peak memory is
    O(q_chunk · k_chunk) scores instead of O(Sq · Sk) — required for the 32k+
    prefill cells. Semantics identical to ``attention``.

    Note: every (q-block, k-block) pair is computed and masked; causal
    block-skipping needs data-dependent trip counts, which is exactly what
    the Pallas kernel (``repro.kernels.flash_attention``) provides on TPU.
    The ~2× causal FLOP overcount of this reference path is visible in the
    roofline's MODEL_FLOPS/HLO_FLOPS ratio and addressed in §Perf.
    """
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    scale = scale if scale is not None else d ** -0.5
    qc = min(q_chunk, sq)
    kc = min(k_chunk, sk)
    if sq % qc != 0 or sk % kc != 0:
        raise ValueError(f"seq lens ({sq},{sk}) not divisible by chunks ({qc},{kc})")
    nq, nk = sq // qc, sk // kc

    from ..distributed.sharding import constrain_batch_dim

    # keep q/k/v in their native dtype; tiles are cast to f32 inside the
    # block bodies (a full-array f32 copy costs GBs/device at 32k).
    # K/V are constrained to batch-only sharding HERE, outside the scan:
    # a seq- or head-sharded K consumed inside the q-chunk loop makes GSPMD
    # re-all-gather it per chunk (64× per layer at 32k — §Perf H2).
    qf = q.reshape(b, nq, qc, kv, g, d)
    kf = constrain_batch_dim(k, 0).reshape(b, nk, kc, kv, d)
    vf = constrain_batch_dim(v, 0).reshape(b, nk, kc, kv, d)
    qp = q_positions.reshape(b, nq, qc)
    kp = k_positions.reshape(b, nk, kc)

    # scan over q blocks (outer), k blocks (inner); each q block is a
    # rematerialization unit — its k-scan residuals (the exp'd score tiles)
    # are recomputed in its own backward window instead of being stored for
    # every (q, k) block pair at once (the flash-attention backward
    # structure; without this, training at 32k seq stores O(nq·nk) score
    # tiles and blows tens of GB per device).
    def q_body(_, qx):
        q_blk, qpos = qx                       # (B,qc,KV,G,D), (B,qc)

        def k_body(carry, kx):
            acc, m, l = carry
            k_blk, v_blk, kpos = kx            # (B,kc,KV,D), ..., (B,kc)
            s = jnp.einsum(
                "bqkgd,bskd->bkgqs",
                q_blk.astype(jnp.float32) * scale,
                k_blk.astype(jnp.float32),
            )  # (B,KV,G,qc,kc)
            qq = qpos[:, None, None, :, None]
            kk = kpos[:, None, None, None, :]
            mask = kk >= 0
            if causal:
                mask = jnp.logical_and(mask, kk <= qq)
            if window > 0:
                mask = jnp.logical_and(mask, qq - kk < window)
            s = jnp.where(mask, s, NEG_INF)
            m_blk = jnp.max(s, axis=-1)                         # (B,KV,G,qc)
            m_new = jnp.maximum(m, m_blk)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, v_blk.astype(jnp.float32)
            )
            return (acc, m_new, l), None

        init = (
            jnp.zeros((b, kv, g, qc, d), jnp.float32),
            jnp.full((b, kv, g, qc), -jnp.inf, jnp.float32),
            jnp.zeros((b, kv, g, qc), jnp.float32),
        )
        (acc, m, l), _ = jax.lax.scan(
            k_body, init, (jnp.swapaxes(kf, 0, 1), jnp.swapaxes(vf, 0, 1), jnp.swapaxes(kp, 0, 1))
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]            # (B,KV,G,qc,D)
        return None, out

    q_blocks = jnp.swapaxes(qf, 0, 1)                            # (nq,B,qc,KV,G,D)
    q_body_r = jax.checkpoint(
        q_body, policy=jax.checkpoint_policies.nothing_saveable
    )
    _, outs = jax.lax.scan(q_body_r, None, (q_blocks, jnp.swapaxes(qp, 0, 1)))
    # outs: (nq, B, KV, G, qc, D) → (B, Sq, H, D)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 2, 3, 1, 4, 5)   # (B,KV,G,nq,qc,D)
    out = out.reshape(b, kv, g, sq, d).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def banded_attention(
    q: jax.Array,                     # (B, Sq, H, D)
    k: jax.Array,                     # (B, Sk, KV, D)
    v: jax.Array,
    *,
    q_positions: jax.Array,
    k_positions: jax.Array,
    window: int,
    causal: bool = True,
    scale: Optional[float] = None,
    q_chunk: int = 512,
) -> jax.Array:
    """Sliding-window attention with a *static* key band per query block.

    For window w and query chunk qc, query block i only needs keys in
    [(i+1)·qc − (w+qc), (i+1)·qc) — a fixed-width band sliced with
    ``dynamic_slice`` (start is traced, width static). FLOPs are
    O(Sq · (w + qc)) instead of the O(Sq · Sk) a masked full computation
    would burn — this is the TPU-friendly SWA prefill structure.
    """
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    scale = scale if scale is not None else d ** -0.5
    qc = min(q_chunk, sq)
    if sq % qc != 0:
        raise ValueError(f"sq={sq} not divisible by q_chunk={qc}")
    nq = sq // qc
    band = window + qc
    if band >= sk:
        return chunked_attention(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, window=window, scale=scale, q_chunk=q_chunk,
        )

    # NOTE: unlike chunked_attention, K/V are NOT gathered here — the band
    # dynamic_slice pulls only O(w + qc) keys per q block, so leaving K/V
    # seq-sharded moves ~band/S of the bytes per chunk (measured 1.6× better
    # than a hoisted full gather for mixtral prefill_32k; §Perf H2).
    qf = q.reshape(b, nq, qc, kv, g, d)
    kf = k
    vf = v

    def q_body(_, qx):
        q_blk, qpos, i = qx               # (B,qc,KV,G,D), (B,qc), scalar
        start = jnp.clip((i + 1) * qc - band, 0, sk - band)
        k_band = jax.lax.dynamic_slice(kf, (0, start, 0, 0), (b, band, kv, d))
        v_band = jax.lax.dynamic_slice(vf, (0, start, 0, 0), (b, band, kv, d))
        kp_band = jax.lax.dynamic_slice(k_positions, (0, start), (b, band))
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs",
            q_blk.astype(jnp.float32) * scale,
            k_band.astype(jnp.float32),
        )
        qq = qpos[:, None, None, :, None]
        kk = kp_band[:, None, None, None, :]
        mask = kk >= 0
        if causal:
            mask = jnp.logical_and(mask, kk <= qq)
        mask = jnp.logical_and(mask, qq - kk < window)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bkgqd", p, v_band.astype(jnp.float32))
        return None, out

    q_body_r = jax.checkpoint(
        q_body, policy=jax.checkpoint_policies.nothing_saveable
    )
    _, outs = jax.lax.scan(
        q_body_r,
        None,
        (
            jnp.swapaxes(qf, 0, 1),
            jnp.swapaxes(q_positions.reshape(b, nq, qc), 0, 1),
            jnp.arange(nq),
        ),
    )
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 2, 3, 1, 4, 5)
    out = out.reshape(b, kv, g, sq, d).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def attention_any(
    q, k, v, *, q_positions, k_positions, causal=True, window=0,
    scale=None, dense_max_seq: int = 2048, q_chunk: int = 512,
) -> jax.Array:
    """Dispatch: dense for short K; banded for long sliding-window; chunked
    (flash-style) otherwise."""
    sk = k.shape[1]
    if sk <= dense_max_seq:
        return attention(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, window=window, scale=scale,
        )
    if window > 0 and window + q_chunk < sk:
        return banded_attention(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            window=window, causal=causal, scale=scale, q_chunk=q_chunk,
        )
    return chunked_attention(
        q, k, v, q_positions=q_positions, k_positions=k_positions,
        causal=causal, window=window, scale=scale, q_chunk=q_chunk,
    )


def attention_paged(
    q: jax.Array,                     # (B, Sq, H, D)
    k_pool: jax.Array,                # (L, P, bs, KV, D) — the whole pool
    v_pool: jax.Array,
    layer: jax.Array,                 # scalar int32 — the layer read
    block_tables: jax.Array,          # (B, MB) int32; -1 = unallocated
    *,
    q_positions: jax.Array,           # (B, Sq) int32
    valid_lengths: jax.Array,         # (B,) int32 — valid tokens per slot,
                                      # counted *after* this step's KV writes
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Attention against one layer of a paged KV pool (reference path).

    Gathers each slot's pages into its logical (MB·bs) sequence and masks
    unallocated/past-length positions via the shared position-based scheme —
    the same contract ``kernels.paged_decode_attention`` implements with
    block-table-indirected DMA on TPU. Serves both chunked prefill (Sq =
    chunk) and decode (Sq = 1) behind the paged cache-layout flag."""
    from .cache import paged_gather_kv, paged_key_positions

    k_ctx, v_ctx = paged_gather_kv(k_pool, v_pool, layer, block_tables)
    k_positions = paged_key_positions(
        block_tables, valid_lengths, k_pool.shape[2]
    )
    return attention(
        q, k_ctx, v_ctx,
        q_positions=q_positions,
        k_positions=k_positions,
        causal=causal,
        scale=scale,
    )


def attention_cross(
    q: jax.Array,                     # (B, Sq, H, D)
    k: jax.Array,                     # (B, Sk, KV, D)
    v: jax.Array,                     # (B, Sk, KV, D)
    k_valid: Optional[jax.Array] = None,   # (B, Sk) bool
    scale: Optional[float] = None,
) -> jax.Array:
    """Bidirectional / cross attention (whisper encoder & cross blocks)."""
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    scale = scale if scale is not None else d ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, kv, g, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    if k_valid is not None:
        scores = jnp.where(k_valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)
