"""Operations and bytes of the fixture architecture `tinymoe`, by the rules of
benchmark/flops.py. `intermediate_size` is ONE expert's width; a token
multiplies the router and `num_experts_per_tok` experts, every expert's
weights are parameters."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops import attention_flops, head_dim, head_params


def attention_params(m: Dict[str, Any]) -> int:
    d, hd = m["hidden_size"], head_dim(m)
    return 2 * d * hd * (m["num_attention_heads"] + m["num_key_value_heads"])


def expert_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def active_layer_weights(m: Dict[str, Any]) -> int:
    """Weights one token multiplies in one block."""
    return (attention_params(m) + m["hidden_size"] * m["num_experts"]
            + m["num_experts_per_tok"] * expert_params(m))


def total_params(m: Dict[str, Any]) -> int:
    d = m["hidden_size"]
    layer = (attention_params(m) + d * m["num_experts"]
             + m["num_experts"] * expert_params(m) + 2 * d)
    return m["num_hidden_layers"] * layer + 2 * head_params(m) + d


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    return 2.0 * (m["num_hidden_layers"] * active_layer_weights(m)
                  + head_params(m))


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    attn = m["num_hidden_layers"] * attention_flops(m, seq, seq, True) / seq
    return 3.0 * (matmul_flops_per_token(m) + attn)


def prefill_flops(m: Dict[str, Any], prompt_len: int) -> float:
    layers = m["num_hidden_layers"]
    return (2.0 * layers * active_layer_weights(m) * prompt_len
            + layers * attention_flops(m, prompt_len, prompt_len, True)
            + 2.0 * head_params(m))


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int) -> Tuple[float, float]:
    """Bytes: every weight once (a full step of 16 slots x top-k touches every
    expert), K and V of each slot's context."""
    layers = m["num_hidden_layers"]
    ops = len(context_lens) * matmul_flops_per_token(m)
    kv_row = 2 * m["num_key_value_heads"] * head_dim(m) * kv_bytes
    byts = float(weight_bytes) * (total_params(m) - head_params(m))
    for c in context_lens:
        ops += layers * attention_flops(m, 1, c, False)
        byts += layers * kv_row * c
    return ops, byts
