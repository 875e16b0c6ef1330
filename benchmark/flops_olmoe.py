"""Operations and bytes of `arch: olmoe`, by the rules at the top of
benchmark/flops.py: what the mathematics requires, a multiply-add is two
operations, causal attention at its lower triangle. `intermediate_size` is
ONE expert's width (the catalog's note on OLMoE's `config.json`). A token
multiplies the router and `num_experts_per_tok` experts; every expert's
weights are parameters. The q/k norms multiply nothing worth counting.

Bytes of the experts are those of the experts TOUCHED, a number the caller
takes from the program's counter (`experts_touched` on the engine's
`serve.engine.decode_dispatch` spans, `touched` on
`serve.engine.prefill_experts`), never all of them by assumption: a decode
step over 16 slots touches about 55 of 64, and bytes counted for weights that
were not read would let a share of the roofline read over 100%.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops import attention_flops, head_dim, head_params


def attention_params(m: Dict[str, Any]) -> int:
    d, hd = m["hidden_size"], head_dim(m)
    return 2 * d * hd * (m["num_attention_heads"] + m["num_key_value_heads"])


def expert_params(m: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: Dict[str, Any]) -> int:
    return m["hidden_size"] * m["num_experts"]


def norm_params(m: Dict[str, Any]) -> int:
    """attn_norm, mlp_norm, and the q and k norms over their projections."""
    d, hd = m["hidden_size"], head_dim(m)
    return 2 * d + hd * (m["num_attention_heads"] + m["num_key_value_heads"])


def active_layer_weights(m: Dict[str, Any]) -> int:
    """Weights one token multiplies in one block."""
    return (attention_params(m) + router_params(m)
            + m["num_experts_per_tok"] * expert_params(m))


def layer_params(m: Dict[str, Any]) -> int:
    return (attention_params(m) + router_params(m)
            + m["num_experts"] * expert_params(m) + norm_params(m))


def total_params(m: Dict[str, Any]) -> int:
    return (m["num_hidden_layers"] * layer_params(m) + 2 * head_params(m)
            + m["hidden_size"])


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    return 2.0 * (m["num_hidden_layers"] * active_layer_weights(m)
                  + head_params(m))


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (3x forward), no recompute."""
    attn = m["num_hidden_layers"] * attention_flops(m, seq, seq, True) / seq
    return 3.0 * (matmul_flops_per_token(m) + attn)


def prefill_flops(m: Dict[str, Any], prompt_len: int) -> float:
    """One prompt's prefill: every position through the blocks, the head at
    the last position only."""
    layers = m["num_hidden_layers"]
    return (2.0 * layers * active_layer_weights(m) * prompt_len
            + layers * attention_flops(m, prompt_len, prompt_len, True)
            + 2.0 * head_params(m))


def experts_ops_bytes(m: Dict[str, Any], assignments: float, touched: float,
                      weight_bytes: int, act_bytes: int) -> Tuple[float, float]:
    """The grouped matmuls of ONE layer (the program's `experts` scope) over
    `assignments` rows (tokens x experts a token) that touch `touched`
    distinct experts: (operations, bytes). Bytes: each touched expert's three
    matrices once, each row read once and its result written once (the
    [rows, intermediate] product between need never leave the chip)."""
    ops = 2.0 * expert_params(m) * assignments
    byts = (touched * expert_params(m) * weight_bytes
            + 2.0 * assignments * m["hidden_size"] * act_bytes)
    return ops, byts


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int, *, experts_touched: float
                          ) -> Tuple[float, float]:
    """One decode step over the live slots with the given context lengths:
    (operations, bytes). Bytes are what must cross HBM once: attention's,
    the router's and the head's weights, the weights of the
    `experts_touched` distinct experts a layer (from the program's counter),
    and K and V of each slot's own context."""
    layers = m["num_hidden_layers"]
    ops = len(context_lens) * matmul_flops_per_token(m)
    kv_row = 2 * m["num_key_value_heads"] * head_dim(m) * kv_bytes
    weights = (layers * (attention_params(m) + router_params(m)
                         + norm_params(m)
                         + experts_touched * expert_params(m))
               + head_params(m) + m["hidden_size"])
    byts = float(weight_bytes) * weights
    for c in context_lens:
        ops += layers * attention_flops(m, 1, c, False)
        byts += layers * kv_row * c
    return ops, byts
