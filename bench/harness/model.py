"""The system under test's model, built from a configuration file, and its
weights, made by the benchmark from the seed.

The program's ``ArchConfig`` comes from ``get_config(program_arch)`` with
every size the file states set by ``dataclasses.replace``, so the file, not
the program's preset, decides what runs. The weights are drawn on the device
in one jitted call, in the served dtype, from the seed alone.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

_MLP_KIND = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}


def arch_config(conf: dict):
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(conf["program_arch"]),
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        mlp_kind=_MLP_KIND[conf["hidden_act"]],
        norm_kind=conf["norm"],
        norm_eps=conf["norm_eps"],
        rope_theta=float(conf["rope_theta"]),
        use_bias=conf["use_bias"],
        tie_embeddings=conf["tie_word_embeddings"],
        sliding_window=0,
        dtype=conf["dtype"],
    )


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds exceed 32 bits)."""
    words = np.random.SeedSequence(abs(int(seed))).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _draw(key, name: str, shape, dtype):
    """One leaf by its role: matrices N(0, 1/fan_in) with fan_in the width
    they contract; the embedding N(0, 0.02^2); norm scales 1 + N(0, 0.1^2);
    norm and linear biases N(0, 0.1^2)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("embedding", "unembed"):
        w = 0.02 * z
    elif name == "scale":
        w = 1.0 + 0.1 * z
    elif name in ("bias", "bq", "bk", "bv", "bo", "b_up", "b_down"):
        w = 0.1 * z
    elif name == "wo":                      # (L, H, hd, d): contracts H*hd
        w = z / math.sqrt(shape[1] * shape[2])
    else:                                   # (L, d_in, ...): contracts d_in
        w = z / math.sqrt(shape[1])
    return w.astype(dtype)


def make_params(model, seed: int):
    """Every weight of ``model``, drawn on the device in one jitted call."""
    from repro.models.layers import ParamDef

    defs = model.param_defs()
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    specs = [
        (path[-1].key, tuple(d.shape), jnp.dtype(d.dtype)) for path, d in flat
    ]

    def draw_all(key):
        keys = jax.random.split(key, len(specs))
        return jax.tree_util.tree_unflatten(
            treedef, [_draw(k, *s) for k, s in zip(keys, specs)]
        )

    return jax.block_until_ready(jax.jit(draw_all)(seed_key(seed)))
