"""The published-width serving launcher and the chip bring-up script, at
smoke size on the CPU: the serve path and its checks, the compile-cache
placement, the device peaks table, and that ``chip_smoke.py`` refuses a
machine without a TPU before doing any work."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.mesh import CHIP_PEAKS, ChipPeaks, chip_peaks
from repro.launch import serve as serve_mod
from repro.launch.serve import (
    CACHE_DIR,
    DECODE_MARGIN_TOL,
    check_published_serve,
    published_config,
    reference_logits,
    serve_published,
)
from repro.models.layers import _fan_in, init_params
from repro.models.transformer import TransformerLM

ROOT = Path(__file__).resolve().parents[1]


def _cpu_env(**extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_published_config_keeps_widths_and_states_the_cut():
    cfg, reduced = published_config("llama_65b")
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size) == (
        8192, 64, 64, 22016, 32000
    )
    assert cfg.n_layers == 4 and reduced == "n_layers 80→4"
    with pytest.raises(ValueError, match="no one-chip depth"):
        published_config("recurrentgemma_9b")


@pytest.mark.parametrize("argv", [
    ["--arch", "recurrentgemma_9b", "--published"],
    ["--arch", "llama_65b", "--published", "--slots", "4"],
])
def test_published_cli_refuses_what_it_would_not_serve(monkeypatch, argv):
    """--published serves only configs with a one-chip depth, and fits the
    slot count itself, so the CLI runs the serve chip_smoke.py runs."""
    monkeypatch.setattr(sys, "argv", ["serve"] + argv)
    with pytest.raises(SystemExit) as exc:
        serve_mod.main()
    assert exc.value.code == 2


@pytest.fixture(scope="module")
def smoke_run():
    """The chip's serve path end to end at smoke size: paged + mixed +
    on-demand + hybrid, slots and pool fitted by memory_analysis."""
    # the CPU has no published peaks: give it a 14 MB "HBM", which the
    # smoke model's programs fill at 4 slots with a full pool (16 and 8
    # slots do not fit at any pool the fit tries)
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(
            CHIP_PEAKS, jax.devices()[0].device_kind,
            ChipPeaks(flops_bf16=1.0, hbm_bw=1.0, hbm_bytes=14e6, ici_bw_per_link=1.0),
        )
        return serve_published(get_smoke_config("llama_65b"), "smoke", n_requests=4)


def test_published_serve_path_and_checks_on_cpu_smoke(smoke_run):
    """Every request at its exact budget, prefill logits on the float32
    reference, every served token of the two replayed requests a near-best
    reference logit."""
    ecfg = smoke_run.engine.cfg
    assert ecfg.kv_layout == "paged" and ecfg.mixed_schedule
    assert ecfg.page_reserve == "ondemand"
    assert ecfg.n_slots == 4 and ecfg.num_pages == 4 * 64
    assert smoke_run.trace.meta["mixed_rounds"] > 0
    out = check_published_serve(smoke_run)
    assert out["requests_completed"] == 4
    assert out["prefill_rel_rms_max"] < 0.03
    longest = max(r.n_decode for r in smoke_run.requests)
    assert out["decode_tokens_checked"] > longest
    assert out["decode_margin_max"] <= DECODE_MARGIN_TOL
    kinds = serve_mod.stage_breakdown(smoke_run.trace)
    assert sum(row["tokens"] for row in kinds.values()) > 0
    assert kinds["mixed"]["rounds"] == smoke_run.trace.meta["mixed_rounds"]


def test_published_check_catches_a_wrong_served_token(smoke_run):
    """A token the engine did not take from its own context (a slot mix-up,
    a wrong KV page) fails the teacher-forced check."""
    longest = max(smoke_run.requests, key=lambda r: (r.n_decode, -r.rid))
    stream = smoke_run.engine.generated[longest.rid]
    good = stream[1]
    stream[1] = (good + 1) % smoke_run.cfg.vocab_size
    try:
        with pytest.raises(RuntimeError, match="served tokens off"):
            check_published_serve(smoke_run)
    finally:
        stream[1] = good


def test_reference_logits_match_the_bf16_forward():
    """The float32 reference takes bf16 weights as they are stored."""
    cfg = get_smoke_config("llama_65b")
    model = TransformerLM(cfg)
    params = init_params(jax.random.key(1), model.param_defs())
    seqs = [np.arange(1, 20, dtype=np.int32), np.arange(5, 12, dtype=np.int32)]
    ref = reference_logits(cfg, params, seqs)
    logits, _ = model.forward(params, np.asarray([seqs[1]]), remat=False)
    assert [r.shape for r in ref] == [(19, cfg.vocab_size), (7, cfg.vocab_size)]
    np.testing.assert_allclose(ref[1], np.asarray(logits[0]), rtol=0.05, atol=0.01)


def test_fan_in_is_the_contracted_input_width():
    """Stacked attention weights scale by the width they contract: wq
    (L, d, H, hd) by d, wo (L, H, hd, d) by H·hd."""
    cfg = get_smoke_config("llama_65b")
    defs = TransformerLM(cfg).param_defs()["blocks"]
    hd = cfg.resolved_head_dim
    assert _fan_in(defs["wq"]) == cfg.d_model
    assert _fan_in(defs["wk"]) == cfg.d_model
    assert _fan_in(defs["wo"]) == cfg.n_heads * hd
    assert _fan_in(defs["mlp"]["w_up"]) == cfg.d_model
    assert _fan_in(defs["mlp"]["w_down"]) == cfg.d_ff
    params = init_params(jax.random.key(0), {"wq": defs["wq"], "wo": defs["wo"]})
    for name, width in (("wq", cfg.d_model), ("wo", cfg.n_heads * hd)):
        std = float(np.std(np.asarray(params[name], np.float32)))
        assert abs(std * np.sqrt(width) - 1.0) < 0.05, (name, std)
        assert params[name].dtype == defs[name].dtype


def test_chip_peaks_table_and_unknown_kind():
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")


def test_chip_smoke_refuses_cpu_before_any_work():
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT, env=_cpu_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "platform: cpu" in r.stdout
    assert "compile cache" not in r.stdout


@pytest.mark.parametrize("outside", [None, "elsewhere"])
def test_compile_cache_placement(tmp_path, outside):
    """Set from outside, JAX_COMPILATION_CACHE_DIR is left to JAX; unset,
    the cache goes to the fixed .jax_cache/ of the checkout."""
    extra = {} if outside is None else {
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / outside)
    }
    code = (
        "import jax; from repro.launch.serve import enable_compile_cache; "
        "print(enable_compile_cache()); "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=_cpu_env(**extra),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    returned, in_jax = r.stdout.split()[-2:]
    want = str(CACHE_DIR) if outside is None else str(tmp_path / outside)
    assert returned == in_jax == want
