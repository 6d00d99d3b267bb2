"""Shared layer primitives for the model zoo.

Parameters are declared as ``ParamDef`` trees (shape + logical axes + init),
so the same declaration serves three consumers:

  * ``init_params``     — materialize real arrays (smoke tests, CPU engine)
  * ``abstract_params`` — ShapeDtypeStructs only (the 512-device dry-run
                          lowers against these; nothing is allocated)
  * ``logical_specs``   — logical-axis tree consumed by
                          ``repro.distributed.sharding`` to build
                          PartitionSpecs for any mesh.

Logical axis vocabulary (mapped to mesh axes by sharding rules):
  "layers"   — scan-stacked layer dim (never sharded)
  "batch"    — data parallel
  "seq"      — sequence (context parallel for long KV)
  "vocab"    — vocabulary rows (TP)
  "embed"    — model width (FSDP axis for 2D weights)
  "heads"    — attention heads (TP)
  "kv_heads" — KV heads
  "head_dim" — per-head width
  "mlp"      — FFN hidden (TP)
  "experts"  — MoE experts (EP)
  "rnn"      — recurrent state width
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Tree = Any


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"       # normal | zeros | ones | embed
    scale: Optional[float] = None

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")


_STACKED_AXES = ("layers", "experts")


def _fan_in(d: ParamDef) -> int:
    """Width of the input a weight contracts: the product of its contracted
    axes. Leading stacked axes ("layers", "experts") are not inputs. A
    weight that reads the model width contracts it alone (``wq`` of shape
    (L, d, H, hd) → d); a weight that writes the model width contracts
    everything before it (``wo`` of shape (L, H, hd, d) → H·hd); any other
    weight contracts its second-to-last axis."""
    lead = 0
    while lead < len(d.axes) and d.axes[lead] in _STACKED_AXES:
        lead += 1
    shape, axes = d.shape[lead:], d.axes[lead:]
    if len(shape) < 2:
        return d.shape[-1]
    if axes[0] == "embed":
        return shape[0]
    if axes[-1] == "embed":
        return math.prod(shape[:-1])
    return shape[-2]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _init_leaf(key, shape, dtype, init, scale):
    """One parameter, generated on the device in its target dtype (one
    compile per leaf shape; the float32 draw is fused into the cast, so no
    float32 copy of a stacked weight is ever held)."""
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(rng: jax.Array, defs: Tree) -> Tree:
    """Materialize a ParamDef tree into real arrays, leaf by leaf."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, d in zip(keys, leaves):
        scale = d.scale
        if scale is None:
            scale = 1.0 if d.init == "embed" else 1.0 / math.sqrt(_fan_in(d))
        out.append(_init_leaf(key, tuple(d.shape), d.dtype, d.init, float(scale)))
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(defs: Tree) -> Tree:
    """ShapeDtypeStruct tree (no allocation) — the dry-run's param stand-in."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def logical_specs(defs: Tree) -> Tree:
    """Logical-axes tree, same structure as the params."""
    return jax.tree_util.tree_map(
        lambda d: d.axes, defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )


# --------------------------------------------------------------------------- #
# Norms                                                                       #
# --------------------------------------------------------------------------- #
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: Optional[jax.Array], eps: float = 1e-5
) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    x = x * weight.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dtype)


def apply_norm(x, p: Dict[str, jax.Array], kind: str, eps: float) -> jax.Array:
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"], eps)
    return layer_norm(x, p["scale"], p.get("bias"), eps)


def norm_defs(d_model: int, kind: str, dtype=jnp.bfloat16, layers: Optional[int] = None):
    lead = (layers,) if layers else ()
    lax = ("layers",) if layers else ()
    defs = {"scale": ParamDef(lead + (d_model,), lax + ("embed",), dtype, "ones")}
    if kind == "layernorm":
        defs["bias"] = ParamDef(lead + (d_model,), lax + ("embed",), dtype, "zeros")
    return defs


# --------------------------------------------------------------------------- #
# Rotary position embeddings (RoPE and M-RoPE)                                #
# --------------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies (head_dim/2,), f32."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(
    x: jax.Array,               # (..., seq, heads, head_dim)
    positions: jax.Array,       # (..., seq) int32
    theta: float = 10000.0,
) -> jax.Array:
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                              # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * inv  # (..., seq, hd/2)
    sin = jnp.sin(angles)[..., None, :]                      # (..., seq, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_m_rope(
    x: jax.Array,               # (batch, seq, heads, head_dim)
    positions: jax.Array,       # (batch, seq, 3) int32 — (t, h, w) triples
    sections: Tuple[int, int, int],
    theta: float = 1_000_000.0,
) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): head_dim/2 frequency slots are split into
    (temporal, height, width) sections, each rotated by its own position."""
    hd = x.shape[-1]
    if sum(sections) != hd // 2:
        raise ValueError(f"M-RoPE sections {sections} must sum to head_dim/2={hd // 2}")
    inv = rope_freqs(hd, theta)                               # (hd/2,)
    # Select which of (t, h, w) drives each frequency slot.
    sel = np.concatenate(
        [np.full(s, idx, dtype=np.int32) for idx, s in enumerate(sections)]
    )
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),                        # (b, s, 3)
        jnp.broadcast_to(jnp.asarray(sel), positions.shape[:-1] + (hd // 2,)).astype(jnp.int32) if False else
        jnp.broadcast_to(jnp.asarray(sel)[None, None, :], positions.shape[:2] + (hd // 2,)),
        axis=-1,
    )                                                         # (b, s, hd/2)
    angles = pos * inv                                        # (b, s, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int) -> jax.Array:
    """Whisper-style fixed sinusoid table (seq, d_model), f32."""
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * dim / d_model)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


# --------------------------------------------------------------------------- #
# MLPs                                                                        #
# --------------------------------------------------------------------------- #
def mlp_defs(
    d_model: int,
    d_ff: int,
    kind: str,
    dtype=jnp.bfloat16,
    layers: Optional[int] = None,
    use_bias: bool = False,
):
    lead = (layers,) if layers else ()
    lax = ("layers",) if layers else ()
    defs: Dict[str, ParamDef] = {}
    if kind == "swiglu":
        defs["w_gate"] = ParamDef(lead + (d_model, d_ff), lax + ("embed", "mlp"), dtype)
        defs["w_up"] = ParamDef(lead + (d_model, d_ff), lax + ("embed", "mlp"), dtype)
        defs["w_down"] = ParamDef(lead + (d_ff, d_model), lax + ("mlp", "embed"), dtype)
    else:  # squared_relu | gelu
        defs["w_up"] = ParamDef(lead + (d_model, d_ff), lax + ("embed", "mlp"), dtype)
        defs["w_down"] = ParamDef(lead + (d_ff, d_model), lax + ("mlp", "embed"), dtype)
        if use_bias:
            defs["b_up"] = ParamDef(lead + (d_ff,), lax + ("mlp",), dtype, "zeros")
            defs["b_down"] = ParamDef(lead + (d_model,), lax + ("embed",), dtype, "zeros")
    return defs


def mlp_apply(x: jax.Array, p: Dict[str, jax.Array], kind: str) -> jax.Array:
    if kind == "swiglu":
        gate = jnp.einsum("...d,df->...f", x, p["w_gate"])
        up = jnp.einsum("...d,df->...f", x, p["w_up"])
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    elif kind == "squared_relu":
        h = jnp.einsum("...d,df->...f", x, p["w_up"])
        if "b_up" in p:
            h = h + p["b_up"]
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
    elif kind == "gelu":
        h = jnp.einsum("...d,df->...f", x, p["w_up"])
        if "b_up" in p:
            h = h + p["b_up"]
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    else:
        raise ValueError(f"unknown mlp kind {kind!r}")
    out = jnp.einsum("...f,fd->...d", h, p["w_down"])
    if "b_down" in p:
        out = out + p["b_down"]
    return out


# --------------------------------------------------------------------------- #
# Embedding / unembedding                                                     #
# --------------------------------------------------------------------------- #
def embed_defs(vocab: int, d_model: int, dtype=jnp.bfloat16, tie: bool = False):
    defs = {
        "embedding": ParamDef((vocab, d_model), ("vocab", "embed"), dtype, "embed", 0.02)
    }
    if not tie:
        defs["unembed"] = ParamDef((d_model, vocab), ("embed", "vocab"), dtype, "embed", 0.02)
    return defs


def embed_tokens(tokens: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    """Embedding lookup with an explicit batch-sharding constraint on the
    output: without it GSPMD picks a pathological sharding for the gather
    from the vocab-sharded table and replicates (B, S, D) activations
    ("involuntary full rematerialization"), costing GBs/device at scale."""
    from ..distributed.sharding import constrain_batch_dim  # noqa: PLC0415

    return constrain_batch_dim(jnp.take(p["embedding"], tokens, axis=0), 0)


def unembed(x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    from ..distributed.sharding import constrain_logits  # noqa: PLC0415

    if "unembed" in p:
        return constrain_logits(jnp.einsum("...d,dv->...v", x, p["unembed"]))
    return constrain_logits(jnp.einsum("...d,vd->...v", x, p["embedding"]))


def moe_aux_weight(cfg) -> float:
    """Load-balancing loss weight (standard 0.01 for Switch-style routers)."""
    return 0.01 if getattr(cfg, "n_experts", 0) > 0 else 0.0


def cross_entropy_loss(
    logits: jax.Array,          # (batch, seq, vocab)
    labels: jax.Array,          # (batch, seq) int32
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
