"""Decode attention — Pallas TPU kernel for single-token GQA attention
against a slot KV cache.

One grid step handles one (batch-slot, kv-head) pair and one cache block:
grid = (B, KV, cache_blocks), cache_blocks innermost/sequential. The g query
heads of the kv head ride together as the MXU's M dim: scores are (g, bk) —
for small g this underfills the MXU's 128 rows, which is exactly the
batching argument the paper's decode cost model encodes (decode is
bandwidth-bound; the roofline confirms it). Online softmax in VMEM scratch,
one (g, D) output tile per (slot, kv-head).

Valid-length masking reads ``lengths`` (B,) from scalar-prefetch memory
(SMEM) — slots in a continuous-batching engine have ragged fill levels, and
a (1, 1) VMEM block of a (B, 1) array breaks the TPU tiling rule.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_kernel(
    length_ref,                     # (B,) int32 SMEM — scalar prefetch
    q_ref,                          # (1, 1, g, D)
    k_ref,                          # (1, 1, bk, D)
    v_ref,
    o_ref,                          # (1, 1, g, D)
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    block_k: int,
    num_k_blocks: int,
):
    ib = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = length_ref[ib]
    k_start = ik * block_k

    @pl.when(k_start < length)
    def _body():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale      # (g, D)
        k = k_ref[0, 0, :, :].astype(jnp.float32)              # (bk, D)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                      # (g, bk)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,                   # (B, H, D) — one new token per slot
    k: jax.Array,                   # (B, KV, S, D) slot cache
    v: jax.Array,
    lengths: jax.Array,             # (B,) int32 — valid entries per slot
    *,
    scale: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    _, kv, s, _ = k.shape
    if h % kv != 0:
        raise ValueError(f"H={h} not divisible by KV={kv}")
    g = h // kv
    scale = scale if scale is not None else d ** -0.5
    bk = min(block_k, s)
    nk = -(-s // bk)                       # grid rounds up; tail block masked
    if s % bk:
        # pad K/V so the tail block's DMA stays in bounds; the padded
        # region sits at positions >= s >= lengths, so the kernel's
        # per-token length mask already hides it. The pad is a full-cache
        # copy per call — serving paths should keep bucketed cache lengths
        # a multiple of block_k (the engine's max_len buckets are); this
        # branch exists so ad-hoc lengths work instead of erroring
        pad = nk * bk - s
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q.reshape(b, kv, g, d)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=bk, num_k_blocks=nk
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, ik, ln: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, ln: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, ln: (ib, ih, ik, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda ib, ih, ik, ln: (ib, ih, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(b, h, d)
