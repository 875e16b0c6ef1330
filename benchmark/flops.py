"""Operations and bytes the algorithm needs, from shapes alone.

Every count is of what the mathematics requires: a multiply-add is two
operations, recomputed work (remat) is not counted, and causal attention is
counted at its lower triangle. `m` is a model dict with the published
`config.json` keys (hidden_size, num_attention_heads, num_key_value_heads,
intermediate_size, vocab_size, num_hidden_layers; head_dim optional).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def head_dim(m: Dict[str, Any]) -> int:
    return int(m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"])


def layer_params(m: Dict[str, Any]) -> int:
    """Weights of one decoder block (norms included)."""
    d, hd = m["hidden_size"], head_dim(m)
    q = d * m["num_attention_heads"] * hd
    kv = 2 * d * m["num_key_value_heads"] * hd
    o = m["num_attention_heads"] * hd * d
    mlp = 3 * d * m["intermediate_size"]
    return q + kv + o + mlp + 2 * d


def head_params(m: Dict[str, Any]) -> int:
    """The output head (the embedding is a lookup and multiplies nothing)."""
    return m["hidden_size"] * m["vocab_size"]


def total_params(m: Dict[str, Any]) -> int:
    return (m["num_hidden_layers"] * layer_params(m) + 2 * head_params(m)
            + m["hidden_size"])


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    """Forward matmul operations for one token: 2 per weight of the blocks'
    matrices and the head."""
    per_layer = layer_params(m) - 2 * m["hidden_size"]
    return 2.0 * (m["num_hidden_layers"] * per_layer + head_params(m))


def attention_flops(m: Dict[str, Any], q_len: int, kv_len: int,
                    causal: bool) -> float:
    """Forward operations of one layer's attention for one sequence: QK^T and
    PV, 2 operations a multiply-add, over the (query, key) pairs that the mask
    keeps. Causal with q_len == kv_len keeps q(q+1)/2 pairs."""
    if causal:
        pairs = q_len * (kv_len - q_len) + q_len * (q_len + 1) / 2.0
    else:
        pairs = float(q_len) * kv_len
    return 4.0 * m["num_attention_heads"] * head_dim(m) * pairs


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (3x forward), no recompute, per token of a
    sequence of `seq` tokens."""
    attn = m["num_hidden_layers"] * attention_flops(m, seq, seq, True) / seq
    return 3.0 * (matmul_flops_per_token(m) + attn)


def prefill_flops(m: Dict[str, Any], prompt_len: int) -> float:
    """One prompt's prefill: every position through the blocks, the head at
    the last position only."""
    per_layer = layer_params(m) - 2 * m["hidden_size"]
    blocks = 2.0 * m["num_hidden_layers"] * per_layer * prompt_len
    attn = m["num_hidden_layers"] * attention_flops(
        m, prompt_len, prompt_len, True)
    return blocks + attn + 2.0 * head_params(m)


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int) -> Tuple[float, float]:
    """One decode step over the live slots with the given context lengths:
    (operations, bytes). Bytes are what must cross HBM once: every weight, and
    K and V of each slot's own context."""
    n = len(context_lens)
    ops = n * matmul_flops_per_token(m)
    kv_row = 2 * m["num_key_value_heads"] * head_dim(m) * kv_bytes
    byts = float(weight_bytes) * (total_params(m) - head_params(m))
    for c in context_lens:
        ops += m["num_hidden_layers"] * attention_flops(m, 1, c, False)
        byts += m["num_hidden_layers"] * kv_row * c
    return ops, byts


def flash_call_ops_bytes(batch: int, heads: int, q_len: int, kv_len: int,
                         hd: int, causal: bool, elem_bytes: int,
                         backward: bool) -> Tuple[float, float]:
    """One call of the flash kernel on [batch, heads, len, hd] operands (K and
    V already repeated to `heads`, as the program passes them): (operations,
    bytes). Forward: QK^T and PV. Backward: the five matmuls dV, dP, dS->dQ,
    dS->dK and the recomputed QK^T, which the algorithm itself needs (flash
    attention stores no probabilities), so 2.5x the forward. Bytes: each
    operand read and each result written once."""
    if causal:
        pairs = q_len * (kv_len - q_len) + q_len * (q_len + 1) / 2.0
    else:
        pairs = float(q_len) * kv_len
    fwd = 4.0 * batch * heads * hd * pairs
    q = batch * heads * q_len * hd * elem_bytes
    kv = batch * heads * kv_len * hd * elem_bytes
    lse = batch * heads * q_len * 4
    if not backward:
        return fwd, q + 2 * kv + q + lse
    # reads q, k, v, o, do, lse; writes dq, dk, dv
    return 2.5 * fwd, (q + 2 * kv + 2 * q + lse) + (q + 2 * kv)
