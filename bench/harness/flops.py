"""Operations and bytes the model's work requires, counted from the
configuration's sizes and the tokens processed -- by the work done, not by
how the program does it. A gathered copy of a slot's block table, a second
copy of the KV pool, padded rows and padded chunk tokens are not work the
model requires and are never counted, so a program that stops doing them
moves its share of the roofline up, and one that does more moves it down.

Sizes come from the configuration file's published keys (``hidden_size``,
``num_attention_heads`` ...), the same dictionary the reference reads.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def gated_mlp(c: dict) -> bool:
    return c["hidden_act"] == "silu"


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies in one layer: q, k, v, o and the MLP."""
    d, f, hd = c["hidden_size"], c["intermediate_size"], head_dim(c)
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    return attn + (3 if gated_mlp(c) else 2) * d * f


def layer_vector_params(c: dict) -> int:
    """Norm scales and biases of one layer."""
    d, f, hd = c["hidden_size"], c["intermediate_size"], head_dim(c)
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    n = 2 * d * (2 if c["norm"] == "layernorm" else 1)
    if c["use_bias"]:
        n += h * hd + 2 * kv * hd + d + f + d
    return n


def weight_bytes(c: dict) -> int:
    """Every weight once, in bf16: what one forward pass over a batch has to
    read from HBM however many tokens it carries."""
    d, v, layers = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    emb = v * d * (1 if c["tie_word_embeddings"] else 2)
    final_norm = d * (2 if c["norm"] == "layernorm" else 1)
    per_layer = layer_matmul_params(c) + layer_vector_params(c)
    return BF16 * (emb + final_norm + layers * per_layer)


def kv_bytes_per_token(c: dict) -> int:
    """Keys and values of one token over every layer, in bf16."""
    return BF16 * 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * head_dim(c)


def attention_flops(c: dict, context: int) -> int:
    """One query token attending ``context`` keys in every layer: q·k and
    p·v, two FLOPs per multiply-add each."""
    return 4 * context * c["num_attention_heads"] * head_dim(c) * c["num_hidden_layers"]


def token_flops(c: dict, context: int, logits: bool) -> int:
    """Model FLOPs of one token at ``context`` keys (itself included);
    ``logits`` adds the output head, which only sampled positions need."""
    n = 2 * c["num_hidden_layers"] * layer_matmul_params(c)
    n += attention_flops(c, context)
    if logits:
        n += 2 * c["hidden_size"] * c["vocab_size"]
    return n


def decode_round(c: dict, contexts: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) one decode round requires: every slot in ``contexts``
    adds one token that attends that many keys (itself included). Bytes are
    the weights once, the KV of the tokens in use, and the new KV written."""
    contexts = list(contexts)
    flops = sum(token_flops(c, n, logits=True) for n in contexts)
    kv = kv_bytes_per_token(c)
    bytes_ = weight_bytes(c) + sum(contexts) * kv + len(contexts) * kv
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peak_flops: float, peak_bw: float) -> float:
    """Roofline time: the larger of the compute bound and the memory bound."""
    return max(flops / peak_flops, bytes_ / peak_bw)
