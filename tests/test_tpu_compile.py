"""Compile checks for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes that
break the tiling rule, programs that do not fit HBM. These tests compile the
serving path's jitted programs at LLaMA-65B widths (depth cut to 2 layers)
and every Pallas kernel under ``repro.kernels`` for one chip of a described
``v5e:2x2`` topology. Nothing runs; shapes come from ``jax.eval_shape``.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the test workers all import
this file."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.rglru import rglru_scan
from repro.launch.mesh import chip_peaks
from repro.models.layers import init_params
from repro.models.transformer import TransformerLM
from repro.serving.engine import serving_jits
from repro.serving.sampler import greedy

N_SLOTS, MAX_LEN, PAGE, CHUNK, ROWS = 16, 1024, 16, 32, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _hbm_total(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    )


@pytest.fixture(scope="module")
def llama_2l(one_chip):
    """LLaMA-65B at published widths, 2 of 80 layers, with the paged pool
    at full capacity for N_SLOTS × MAX_LEN — all as abstract shapes."""
    cfg = dataclasses.replace(get_config("llama_65b"), n_layers=2)
    model = TransformerLM(cfg)
    defs = model.param_defs()
    params = jax.eval_shape(lambda k: init_params(k, defs), jax.random.key(0))
    mb = MAX_LEN // PAGE
    cache = jax.eval_shape(
        lambda: model.paged_cache_init(N_SLOTS * mb, PAGE, N_SLOTS, mb)
    )
    return model, _on(one_chip, params), _on(one_chip, cache)


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _bool(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.bool_, sharding=sharding)


def test_fused_decode_compiles_at_llama65b_widths(one_chip, llama_2l):
    model, params, cache = llama_2l
    _, _, fused = serving_jits(model, greedy, None)
    s = one_chip
    compiled = fused.lower(
        8, params, _i32(s, N_SLOTS), cache, _bool(s, N_SLOTS),
        _i32(s, N_SLOTS), _i32(s, N_SLOTS), _i32(s, N_SLOTS), None,
    ).compile()
    assert _hbm_total(compiled) < chip_peaks("TPU v5 lite").hbm_bytes


def test_mixed_step_compiles_at_llama65b_widths(one_chip, llama_2l):
    model, params, cache = llama_2l
    _, mixed, _ = serving_jits(model, greedy, None)
    s, j, r = one_chip, N_SLOTS, ROWS
    compiled = mixed.lower(
        params, _i32(s, j), cache, _i32(s, r, CHUNK), _i32(s, r), _i32(s, r),
        _i32(s, r), _bool(s, j), _i32(s, j + r), _i32(s, j + r),
        _bool(s, j + r), None,
    ).compile()
    assert _hbm_total(compiled) < chip_peaks("TPU v5 lite").hbm_bytes


def _kernel_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _bf16(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)


def test_paged_decode_attention_kernel_compiles(one_chip):
    s, cfg = one_chip, get_config("llama_65b")
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    mb = MAX_LEN // PAGE
    text = _kernel_text(
        paged_decode_attention,
        _bf16(s, N_SLOTS, h, d), _bf16(s, kv, N_SLOTS * mb, PAGE, d),
        _bf16(s, kv, N_SLOTS * mb, PAGE, d),
        _i32(s, N_SLOTS, mb), _i32(s, N_SLOTS),
    )
    assert "tpu_custom_call" in text


def test_decode_attention_kernel_compiles(one_chip):
    s, cfg = one_chip, get_config("llama_65b")
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    text = _kernel_text(
        decode_attention,
        _bf16(s, N_SLOTS, h, d), _bf16(s, N_SLOTS, kv, MAX_LEN, d),
        _bf16(s, N_SLOTS, kv, MAX_LEN, d), _i32(s, N_SLOTS),
    )
    assert "tpu_custom_call" in text


def test_flash_attention_kernel_compiles(one_chip):
    s, cfg = one_chip, get_config("llama_65b")
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    text = _kernel_text(
        flash_attention,
        _bf16(s, 1, h, MAX_LEN, d), _bf16(s, 1, kv, MAX_LEN, d),
        _bf16(s, 1, kv, MAX_LEN, d),
    )
    assert "tpu_custom_call" in text


def test_rglru_kernel_compiles(one_chip):
    """At recurrentgemma-9b's recurrence width (d_model; rnn_width unset)."""
    s, cfg = one_chip, get_config("recurrentgemma_9b")
    r = cfg.rnn_width or cfg.d_model
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=s)
    text = _kernel_text(rglru_scan, f32(4, 512, r), f32(4, 512, r), f32(4, r))
    assert "tpu_custom_call" in text
