"""Compile checks for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes that
break the tiling rule, programs that do not fit HBM. These tests compile the
serving path's jitted programs at LLaMA-65B and Granite-3-8B widths (depth
cut to 2 layers) and every Pallas kernel under ``repro.kernels`` for one
chip of a described ``v5e:2x2`` topology. Nothing runs; shapes come from
``jax.eval_shape``. The serving programs must update the donated KV pool in
place: no copy of the whole pool, and temporaries smaller than the pool.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the test workers all import
this file."""
import dataclasses
import math
import os
import re

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
# Granite at the benchmark cell's 32 slots, over 3/4 of a full pool
GRANITE_SLOTS, GRANITE_PAGES = 32, 1536
PROGRAMS = ("fused_decode", "mixed_round", "chunk_round")
WIDTHS = ("llama_2l", "granite_2l")


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


def _paged_model(sharding, arch, n_slots, num_pages):
    """``arch`` at its widths, 2 layers, and a paged pool of ``num_pages``
    for ``n_slots`` × MAX_LEN — all as abstract shapes."""
    cfg = dataclasses.replace(get_config(arch), n_layers=2)
    model = TransformerLM(cfg)
    defs = model.param_defs()
    params = jax.eval_shape(lambda k: init_params(k, defs), jax.random.key(0))
    mb = MAX_LEN // PAGE
    cache = jax.eval_shape(
        lambda: model.paged_cache_init(num_pages, PAGE, n_slots, mb)
    )
    return model, _on(sharding, params), _on(sharding, cache)


@pytest.fixture(scope="module")
def llama_2l(one_chip):
    """LLaMA-65B at published widths (64 KV heads, no grouping), 2 of 80
    layers, with the pool at full capacity for N_SLOTS × MAX_LEN."""
    return _paged_model(one_chip, "llama_65b", N_SLOTS, N_SLOTS * MAX_LEN // PAGE)


@pytest.fixture(scope="module")
def granite_2l(one_chip):
    """Granite-3-8B at published widths, 2 of 40 layers, 32 slots."""
    return _paged_model(one_chip, "granite_3_8b", GRANITE_SLOTS, GRANITE_PAGES)


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _bool(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.bool_, sharding=sharding)


def _lower(program, s, model, params, cache, horizon):
    """``program`` of the engine's serving jits against abstract shapes: the
    slot count is the pool's, and the mixed and chunk rounds take ROWS rows
    of CHUNK tokens."""
    chunk, mixed, fused = serving_jits(model, greedy, None)
    j, r = cache["block_tables"].shape[0], ROWS
    if program == "fused_decode":
        return fused.lower(
            horizon, params, _i32(s, j), cache, _bool(s, j),
            _i32(s, j), _i32(s, j), _i32(s, j), None,
        )
    if program == "mixed_round":
        return mixed.lower(
            params, _i32(s, j), cache, _i32(s, r, CHUNK), _i32(s, r),
            _i32(s, r), _i32(s, r), _bool(s, j), _i32(s, j + r),
            _i32(s, j + r), _bool(s, j + r), None,
        )
    return chunk.lower(params, _i32(s, r, CHUNK), cache, _i32(s, r), _i32(s, r), _i32(s, r))


@pytest.fixture(scope="module")
def compiled(request, one_chip):
    """Compile each (width, program, horizon) once for the whole module."""
    memo = {}

    def get(width, program, horizon=8):
        key = (width, program, horizon if program == "fused_decode" else None)
        if key not in memo:
            model, params, cache = request.getfixturevalue(width)
            memo[key] = _lower(
                program, one_chip, model, params, cache, horizon
            ).compile()
        return memo[key]

    return get


_INSTR = re.compile(
    r"%(?P<name>[\w.\-]+) = \w+\[(?P<dims>[\d,]*)\]\{[^}]*\} (?P<op>[\w\-]+)\("
)


def _pool_copies(text, pool_shape):
    """Names of the copies (``copy``, ``copy-done``, or a fusion the compiler
    named for a copy) whose result holds as many elements as a whole K or V
    pool, in any layout: a pool that is rebuilt rather than updated."""
    n = math.prod(pool_shape)
    out = []
    for m in _INSTR.finditer(text):
        is_copy = m["op"] in ("copy", "copy-done") or (
            m["op"] == "fusion" and "copy" in m["name"]
        )
        dims = [int(d) for d in m["dims"].split(",") if d]
        if is_copy and math.prod(dims) == n:
            out.append(m["name"])
    return out


def test_fused_decode_compiles_at_llama65b_widths(llama_2l, compiled):
    c = compiled("llama_2l", "fused_decode")
    assert _hbm_total(c) < chip_peaks("TPU v5 lite").hbm_bytes
    assert _pool_copies(c.as_text(), llama_2l[2]["k"].shape) == []


def test_mixed_step_compiles_at_llama65b_widths(llama_2l, compiled):
    c = compiled("llama_2l", "mixed_round")
    assert _hbm_total(c) < chip_peaks("TPU v5 lite").hbm_bytes
    assert _pool_copies(c.as_text(), llama_2l[2]["k"].shape) == []


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("width", WIDTHS)
def test_serving_program_never_copies_the_pool(request, compiled, width, program):
    """The layer scans carry the pool and scatter each layer's K/V into it:
    no program holds a copy of the whole pool (the fused decode at the
    horizon the pool fit compiles, 8)."""
    cache = request.getfixturevalue(width)[2]
    text = compiled(width, program).as_text()
    assert _pool_copies(text, cache["k"].shape) == []


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("width", WIDTHS)
def test_serving_program_temporaries_below_pool(request, compiled, width, program):
    """Temporaries stay below the K+V pool's bytes. The fused decode is
    taken at one round per dispatch, the benchmark cell's horizon: at
    horizon 8 and 64 KV heads, the attention's float32 copy of every slot's
    gathered block table (the dense gather) outgrows the pool by itself."""
    cache = request.getfixturevalue(width)[2]
    pool_bytes = 2 * cache["k"].size * cache["k"].dtype.itemsize
    c = compiled(width, program, horizon=1)
    assert c.memory_analysis().temp_size_in_bytes < pool_bytes


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
