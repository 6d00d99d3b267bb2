"""The plain reference: a dense decoder-only transformer's forward pass in
float32, written from the published equations and nothing of the program.

Each layer: pre-norm (RMSNorm, or LayerNorm with bias), q/k/v projections
(with biases where the configuration has them), rotary embeddings in the
rotate-half form of the Hugging Face models, causal grouped-query attention
scaled by 1/sqrt(head_dim), output projection, residual; pre-norm, MLP
(SwiGLU for ``silu``, tanh-approximated GELU with biases for
``gelu_pytorch_tanh``), residual. Then the final norm and the output head
(the embedding's transpose when tied). Every matrix product runs at
float32's full precision.

The weights are the ones the benchmark drew from the seed, read by name;
one layer's float32 copy exists at a time. ``quant="fp8"`` is the control:
the same forward with both operands of every matrix product rounded to
float8 (e4m3, scaled to its range per tensor), the precision below the
configuration's bf16 that a later PR might be tempted by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q(x, quant):
    """Round ``x`` to fp8 with a per-tensor scale, back in float32."""
    if quant is None:
        return x
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(F8).astype(jnp.float32) / s


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant))


def _norm(x, p, conf):
    if conf["norm"] == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + conf["norm_eps"]) * p["scale"]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + conf["norm_eps"]) * p["scale"] + p["bias"]


def _rope(x, pos, theta):
    """x: (S, H, D). Rotate-half rotary embedding."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend(q, k, v, quant):
    """Causal grouped-query attention. q: (S, H, D); k, v: (S, KV, D).
    Queries run in blocks of ``Q_BLOCK`` so the scores fit."""
    s, h, d = q.shape
    kv = k.shape[1]
    g = h // kv
    q = q.reshape(s // Q_BLOCK, Q_BLOCK, kv, g, d) / np.sqrt(d)
    kpos = jnp.arange(s)

    def block(args):
        qb, start = args
        sc = _mm("qkgd,skd->kgqs", qb, k, quant)
        qpos = start + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return _mm("kgqs,skd->qkgd", pr, v, quant)

    starts = jnp.arange(0, s, Q_BLOCK)
    out = jax.lax.map(block, (q, starts))
    return out.reshape(s, h, d)


def _mlp(x, p, conf, quant):
    if conf["hidden_act"] == "silu":
        gate = _mm("sd,df->sf", x, p["w_gate"], quant)
        up = _mm("sd,df->sf", x, p["w_up"], quant)
        h = jax.nn.silu(gate) * up
    else:
        h = _mm("sd,df->sf", x, p["w_up"], quant) + p["b_up"]
        c = np.sqrt(2.0 / np.pi)
        h = 0.5 * h * (1.0 + jnp.tanh(c * (h + 0.044715 * h ** 3)))
    out = _mm("sf,fd->sd", h, p["w_down"], quant)
    if conf["use_bias"]:
        out = out + p["b_down"]
    return out


def _layer(conf, quant, h, lp):
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    s = h.shape[0]
    pos = jnp.arange(s)
    x = _norm(h, lp["norm_attn"], conf)
    q = _mm("sd,dhk->shk", x, lp["wq"], quant)
    k = _mm("sd,dhk->shk", x, lp["wk"], quant)
    v = _mm("sd,dhk->shk", x, lp["wv"], quant)
    if conf["use_bias"]:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = _rope(q, pos, conf["rope_theta"])
    k = _rope(k, pos, conf["rope_theta"])
    a = _mm("shk,hkd->sd", _attend(q, k, v, quant), lp["wo"], quant)
    if conf["use_bias"]:
        a = a + lp["bo"]
    h = h + a
    return h + _mlp(_norm(h, lp["norm_mlp"], conf), lp["mlp"], conf, quant)


def _embed(emb, tokens):
    return emb[tokens].astype(jnp.float32)


def _head(conf, quant, h, final, emb):
    h = _norm(h, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), final), conf)
    w = emb["embedding"].astype(jnp.float32)
    if conf["tie_word_embeddings"]:
        return _mm("sd,vd->sv", h, w, quant)
    return _mm("sd,dv->sv", h, emb["unembed"].astype(jnp.float32), quant)


def _static(conf: dict):
    keys = ("norm", "norm_eps", "hidden_act", "use_bias", "rope_theta",
            "tie_word_embeddings")
    return tuple((k, conf[k]) for k in keys)


@functools.lru_cache(maxsize=None)
def _programs(static, quant):
    conf = dict(static)
    return (
        jax.jit(_embed),
        jax.jit(functools.partial(_layer, conf, quant)),
        jax.jit(functools.partial(_head, conf, quant)),
    )


def logits(conf: dict, params, tokens: np.ndarray, pad_to: int, quant=None) -> jax.Array:
    """Logits (pad_to, V) at every position of ``tokens`` (right-padded to
    ``pad_to``, a multiple of ``Q_BLOCK``; padding never reaches an earlier
    position under the causal mask). Layer by layer, so one layer's float32
    weights exist at a time; the result stays on the device."""
    if pad_to % Q_BLOCK:
        raise ValueError(f"pad_to {pad_to} is not a multiple of {Q_BLOCK}")
    toks = np.zeros(pad_to, np.int32)
    toks[: len(tokens)] = tokens
    embed, layer, head = _programs(_static(conf), quant)
    with jax.default_matmul_precision("highest"):
        h = embed(params["embed"]["embedding"], jnp.asarray(toks))
        blocks = params["blocks"]
        for i in range(conf["num_hidden_layers"]):
            lp = jax.tree_util.tree_map(lambda a: a[i], blocks)
            h = layer(h, lp)
        return head(h, params["norm_final"], params["embed"])
