"""The harness end to end, on the CPU at a toy size: ``run.py`` refuses to
run without a TPU; past the look for a chip, a sound serve is correct and a
serve whose tokens are altered where they are produced is not; nothing
compiles inside the window; the fp8 control reads far wider gaps than the
program."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from harness import check, drive
from harness.cell import load_cell
from harness.peaks import Peaks
from harness.runner import run_cell

ROOT = Path(__file__).resolve().parents[2]

TOY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=160, vocab_size=256, num_hidden_layers=2,
)
TOY_MIX = dict(
    n_requests=400, max_len=128,
    prompt={"dist": "normal", "mean": 20, "std": 6, "min": 1, "max": 40},
    output={"dist": "normal", "mean": 24, "std": 10, "min": 1, "max": 48},
    check={"served_tokens": 60, "max_requests": 3},
)

def _toy_cell(name="granite-3-8b.gsm8k-backlog"):
    cell = load_cell(name, ROOT)
    cell.config.update(TOY_MODEL)
    cell.traffic.update(TOY_MIX)
    cell.max_slots = 4
    return cell


def _alter_tokens(monkeypatch):
    """Every token the fused decode and the mixed round produce, shifted by
    one: wrong where it is produced, still in the vocabulary."""
    from repro.models.transformer import TransformerLM

    fused, mixed = TransformerLM.decode_steps, TransformerLM.mixed_step

    def altered_fused(self, *a, **k):
        block, emitted, active, last, cache = fused(self, *a, **k)
        v = self.cfg.vocab_size
        block = jnp.where(block >= 0, (block + 1) % v, block)
        return block, emitted, active, (last + 1) % v, cache

    def altered_mixed(self, *a, **k):
        sampled, cache = mixed(self, *a, **k)
        return jnp.where(sampled >= 0, (sampled + 1) % self.cfg.vocab_size, sampled), cache

    monkeypatch.setattr(TransformerLM, "decode_steps", altered_fused)
    monkeypatch.setattr(TransformerLM, "mixed_step", altered_mixed)


def _run(cell, seed=2**35 + 3):
    return run_cell(cell, seed, 2.0, False, time.perf_counter(),
                    devices=jax.devices(), peaks=Peaks(197e12, 819e9, 1e12),
                    log=lambda *_: None)


@pytest.fixture(autouse=True)
def _no_repo_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "granite-3-8b.gsm8k-backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert "metrics" not in p.stdout


def test_sound_serve_is_correct():
    line, lines = _run(_toy_cell())
    assert line["correct"] is True
    assert set(line["metrics"]) == {"output_tok_s", "tbt_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    assert line["checks"]["token_gap_max"]["value"] <= line["checks"]["token_gap_max"]["limit"]
    assert lines[-1] == "correct: True"


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    _alter_tokens(monkeypatch)
    line, lines = _run(_toy_cell())
    assert line["correct"] is False
    assert line["checks"]["token_gap_max"]["value"] > line["checks"]["token_gap_max"]["limit"]
    assert lines[-1] == "correct: False"


def test_nothing_compiles_inside_the_window():
    cell = load_cell("granite-3-8b.gsm8k-backlog", ROOT)
    cell.config.update(TOY_MODEL)             # the mix's own lengths: requests finish
    setup = drive.build(cell.config, cell.traffic, 9, 1e12, 8)
    run = drive.serve(setup, 9, 6.0)
    assert any(r.done is not None and run.in_window(r.done) for r in run.reqs.values())
    assert run.window_compiles == []


def test_fp8_control_reads_wider_gaps_than_the_program():
    cell = _toy_cell()
    setup = drive.build(cell.config, cell.traffic, 5, 1e12, cell.max_slots)
    run = drive.serve(setup, 5, 2.0)
    generated = {rid: list(t) for rid, t in setup.engine.generated.items()}
    nums = check.check(run, 5, setup.params, generated, control=True)
    assert nums["checked_tokens"] > 0
    assert nums["control_gap_max"] > 3 * max(nums["token_gap_max"], 1e-3)
