"""RG-LRU linear recurrence — Pallas TPU kernel.

The RecurrentGemma recurrence h_t = a_t ⊙ h_{t-1} + x_t is a per-channel
linear scan: embarrassingly parallel across channels, strictly sequential in
time. The jnp baseline lowers to a length-S ``lax.scan`` whose per-step work
(element-wise over R channels) is far too small to hide HBM latency — the
kernel instead:

  * blocks channels over the grid (each grid step owns R_blk channels,
    VPU-lane-aligned at 128), and
  * streams S_blk × R_blk tiles of (a, x) into VMEM, scanning time *inside*
    the block with the carry in a VMEM scratch register — one DMA per tile
    instead of one per step (S_blk× fewer round trips).

Grid = (B, R_blocks, S_blocks); S innermost/sequential so the carry flows.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(
    a_ref,                           # (1, bs, br) f32 decay
    x_ref,                           # (1, bs, br) f32 gated input
    h0_ref,                          # (1, 1, br) f32 initial state
    o_ref,                           # (1, bs, br)
    hN_ref,                          # (1, 1, br) final state
    carry_ref,                       # VMEM scratch (1, br)
    *,
    block_s: int,
    num_s_blocks: int,
):
    is_ = pl.program_id(2)

    @pl.when(is_ == 0)
    def _init():
        carry_ref[...] = h0_ref[0]

    def step(t, h):
        # one (1, br) row per time step, read and written through the refs
        # (Mosaic lowers a dynamic row slice of a ref, not of a value)
        row = pl.ds(t, 1)
        h_new = a_ref[0, row, :] * h + x_ref[0, row, :]
        o_ref[0, row, :] = h_new.astype(o_ref.dtype)
        return h_new

    h = jax.lax.fori_loop(0, block_s, step, carry_ref[...])
    carry_ref[...] = h

    @pl.when(is_ == num_s_blocks - 1)
    def _finish():
        hN_ref[0] = h.astype(hN_ref.dtype)


def rglru_scan(
    a: jax.Array,                    # (B, S, R) decay in (0,1)
    x: jax.Array,                    # (B, S, R) gated input
    h0: Optional[jax.Array] = None,  # (B, R)
    *,
    block_s: int = 256,
    block_r: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (h (B,S,R), h_final (B,R)), all f32."""
    b, s, r = a.shape
    if h0 is None:
        h0 = jnp.zeros((b, r), jnp.float32)
    bs = min(block_s, s)
    br = min(block_r, r)
    if s % bs or r % br:
        raise ValueError(f"(S,R)=({s},{r}) must divide blocks ({bs},{br})")
    ns, nr = s // bs, r // br
    # the state rides as (B, 1, R): its (1, br) blocks then satisfy the TPU
    # tiling rule (last two block dims divisible by (8, 128) or equal to
    # the array's), which a (1, br) block of a (B, R) array breaks
    h0 = h0.reshape(b, 1, r)

    kernel = functools.partial(_rglru_kernel, block_s=bs, num_s_blocks=ns)
    out, h_fin = pl.pallas_call(
        kernel,
        grid=(b, nr, ns),
        in_specs=[
            pl.BlockSpec((1, bs, br), lambda ib, ir, is_: (ib, is_, ir)),
            pl.BlockSpec((1, bs, br), lambda ib, ir, is_: (ib, is_, ir)),
            pl.BlockSpec((1, 1, br), lambda ib, ir, is_: (ib, 0, ir)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, br), lambda ib, ir, is_: (ib, is_, ir)),
            pl.BlockSpec((1, 1, br), lambda ib, ir, is_: (ib, 0, ir)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, r), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, r), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, br), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), x.astype(jnp.float32), h0.astype(jnp.float32))
    return out, h_fin.reshape(b, r)
