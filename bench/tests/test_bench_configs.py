"""The configuration file holds the published widths and records its cuts,
and BENCHMARK.json's pieces are all found by name."""
import json
from pathlib import Path

import pytest

from harness import cell as cell_mod
from harness.model import arch_config

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

PUBLISHED = {
    "granite-3-8b": dict(
        hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
        head_dim=128, intermediate_size=12800, vocab_size=49155,
        hidden_act="silu", norm="rmsnorm", use_bias=False, layers=(40, 20),
    ),
}


def _conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_widths_as_published(name):
    conf, want = _conf(name), dict(PUBLISHED[name])
    full, cut = want.pop("layers")
    for k, v in want.items():
        assert conf[k] == v, k
    assert conf["published"]["num_hidden_layers"] == full
    assert conf["num_hidden_layers"] == cut
    for k in ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "vocab_size"):
        assert conf["published"][k] == conf[k], k


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_cuts_are_recorded(name):
    conf = _conf(name)
    entry = {c["name"]: c for c in BENCH["configs"]}.get(name)
    if entry is not None:
        assert sorted(entry["reduced"]) == sorted(conf["reduced"])
        assert entry["file"] == f"bench/configs/{name}.json"
    for key in ("source", "deployment", "assumed", "departures", "reduced"):
        assert conf[key], key


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_program_config_takes_every_width(name):
    conf = _conf(name)
    cfg = arch_config(conf)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            cfg.d_ff, cfg.vocab_size, cfg.n_layers) == (
        conf["hidden_size"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["head_dim"],
        conf["intermediate_size"], conf["vocab_size"], conf["num_hidden_layers"])
    assert cfg.tie_embeddings == conf["tie_word_embeddings"]
    assert cfg.rope_theta == conf["rope_theta"] and cfg.norm_eps == conf["norm_eps"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_piece_found_by_name(cell):
    c = cell_mod.load_cell(cell, ROOT)
    assert c.chips == 1 and c.limits["token_gap_max"] > 0 and c.max_slots >= 1
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(cell_mod.reader(m.name, ROOT))
    moved = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    for m in c.per_layer:
        assert moved[m.name] in names, m.name


def test_benchmark_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert {m["name"] for m in BENCH["end_to_end"]} <= {
        "output_tok_s", "ttft_p90_ms", "tbt_p95_ms", "setup_s"}
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200
