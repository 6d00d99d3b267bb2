"""Operation and byte counts against hand arithmetic: at Granite's shapes,
and at StarCoder2-7B's published ones (LayerNorm, GELU, biases), the dense
variant the counts also cover."""
import json
from pathlib import Path

from harness import flops

ROOT = Path(__file__).resolve().parents[2]


def _conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_granite_counts():
    c = _conf("granite-3-8b")
    # q, o: 4096x4096 each; k, v: 4096x1024 each; SwiGLU: 3 x 4096x12800
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12800
    assert flops.layer_matmul_params(c) == per_layer == 199_229_440
    # 20 layers, two RMSNorm scales each, the tied embedding, the final norm
    weights = 20 * (per_layer + 2 * 4096) + 49155 * 4096 + 4096
    assert flops.weight_bytes(c) == 2 * weights == 8_372_191_232
    assert flops.kv_bytes_per_token(c) == 2 * 2 * 20 * 8 * 128 == 81_920
    assert flops.attention_flops(c, 100) == 4 * 100 * 32 * 128 * 20
    assert flops.token_flops(c, 1, logits=True) == (
        2 * 20 * per_layer + 4 * 32 * 128 * 20 + 2 * 4096 * 49155)


STARCODER2_7B = dict(
    hidden_size=4608, num_attention_heads=36, num_key_value_heads=4, head_dim=128,
    intermediate_size=18432, vocab_size=49152, num_hidden_layers=16,
    hidden_act="gelu_pytorch_tanh", norm="layernorm", use_bias=True,
    tie_word_embeddings=True,
)


def test_starcoder2_counts():
    c = STARCODER2_7B
    # q, o: 4608x4608; k, v: 4608x512; GELU MLP: 2 x 4608x18432
    per_layer = 2 * 4608 * 4608 + 2 * 4608 * 512 + 2 * 4608 * 18432
    assert flops.layer_matmul_params(c) == per_layer == 217_055_232
    # LayerNorm scale+bias twice; biases of q, k, v, o and the MLP
    vec = 2 * 2 * 4608 + (4608 + 2 * 512 + 4608 + 18432 + 4608)
    weights = 16 * (per_layer + vec) + 49152 * 4608 + 2 * 4608
    assert flops.weight_bytes(c) == 2 * weights
    assert flops.kv_bytes_per_token(c) == 2 * 2 * 16 * 4 * 128 == 32_768


def test_decode_round_and_roofline():
    c = STARCODER2_7B
    f, b = flops.decode_round(c, [10, 20])
    assert f == flops.token_flops(c, 10, True) + flops.token_flops(c, 20, True)
    assert b == flops.weight_bytes(c) + (30 + 2) * 32_768
    t = flops.least_seconds(f, b, 197e12, 819e9)
    assert t == max(f / 197e12, b / 819e9) == b / 819e9   # two slots: memory-bound
