"""Operations and bytes of `arch: lfm2` (LFM2-24B-A2B), by the rules at the
top of benchmark/flops.py: what the mathematics requires, a multiply-add is
two operations, causal attention at its lower triangle. What this stack
changes: `num_hidden_layers` counts THREE kinds of layer. A layer whose
`layer_types` entry is "conv" has the gated short convolution in the place of
attention (an input projection to 3 x hidden, `conv_L_cache` taps a channel,
an output projection) and keeps no K and V; only the "full_attention" layers
(`attention_layers`) have q, k, v, o and pages. The first `num_dense_layers`
layers have the dense feed-forward of `intermediate_size`; the rest
(`layers(m)[1]`) a router over `num_experts` experts of
`moe_intermediate_size`, `num_experts_per_tok` a token. The head is the
embedding, counted once as parameters and once as a matmul.

Bytes of the experts are those of the experts TOUCHED, from the program's
counters (`experts_touched` on `serve.engine.decode_dispatch`, `touched` on
`serve.engine.prefill_experts`), never all of them by assumption.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops import head_dim, head_params


def layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(dense, sparse): the leading layers with the dense feed-forward, and
    the layers with the router and the experts."""
    dense = min(m["num_dense_layers"], m["num_hidden_layers"])
    return dense, m["num_hidden_layers"] - dense


def attention_layers(m: Dict[str, Any]) -> int:
    return sum(1 for kind in m["layer_types"] if kind == "full_attention")


def conv_layers(m: Dict[str, Any]) -> int:
    return m["num_hidden_layers"] - attention_layers(m)


def attention_params(m: Dict[str, Any]) -> int:
    d, hd = m["hidden_size"], head_dim(m)
    return 2 * d * hd * (m["num_attention_heads"] + m["num_key_value_heads"])


def conv_matmul_params(m: Dict[str, Any]) -> int:
    """The conv operator's two projections."""
    d = m["hidden_size"]
    return d * 3 * d + d * d


def conv_params(m: Dict[str, Any]) -> int:
    return conv_matmul_params(m) + m["conv_L_cache"] * m["hidden_size"]


def expert_params(m: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_ffn_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: Dict[str, Any]) -> int:
    """The router's matrix and its selection bias."""
    return (m["hidden_size"] + 1) * m["num_experts"]


def total_params(m: Dict[str, Any]) -> int:
    dense, sparse = layers(m)
    la, lc, d = attention_layers(m), conv_layers(m), m["hidden_size"]
    return (la * (attention_params(m) + 2 * head_dim(m)) + lc * conv_params(m)
            + dense * dense_ffn_params(m)
            + sparse * (router_params(m)
                        + m["num_experts"] * expert_params(m))
            + m["num_hidden_layers"] * 2 * d + head_params(m) + d)


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    dense, sparse = layers(m)
    return 2.0 * (attention_layers(m) * attention_params(m)
                  + conv_layers(m) * conv_matmul_params(m)
                  + dense * dense_ffn_params(m)
                  + sparse * (m["hidden_size"] * m["num_experts"]
                              + m["num_experts_per_tok"] * expert_params(m))
                  + head_params(m))


def attention_flops(m: Dict[str, Any], pairs: float) -> float:
    """QK^T and PV of ONE layer over `pairs` (query, key) pairs a head."""
    return 4.0 * m["num_attention_heads"] * head_dim(m) * pairs


def causal_pairs(s: int) -> float:
    return s * (s + 1) / 2.0


def conv_ops_bytes(m: Dict[str, Any], rows: float, act_bytes: int
                   ) -> Tuple[float, float]:
    """ONE conv layer's operator less its two projections over `rows` rows
    (the program's scope `conv` and the element-wise parts of `conv_in` and
    `conv_out`): (operations, bytes). B * X, the taps' multiply-adds and the
    gate a (row, channel); bytes: B, C and X read and the gated result
    written once, the taps' weights."""
    d, k = m["hidden_size"], m["conv_L_cache"]
    return ((2.0 + 2.0 * k) * rows * d,
            float(4 * rows * d * act_bytes + k * d * act_bytes))


def conv_state_bytes(m: Dict[str, Any], act_bytes: int) -> int:
    """ONE slot's window in ONE conv layer: the convolution's last
    `conv_L_cache - 1` inputs."""
    return (m["conv_L_cache"] - 1) * m["hidden_size"] * act_bytes


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (3x forward), no recompute; the program has no
    Train path over conv layers, the count is the mathematics'."""
    per_row = (attention_layers(m) * attention_flops(m, causal_pairs(seq))
               / seq + conv_layers(m) * conv_ops_bytes(m, 1, 2)[0])
    return 3.0 * (matmul_flops_per_token(m) + per_row)


def prefill_flops(m: Dict[str, Any], prompt_len: int) -> float:
    """One prompt's prefill: every position through the blocks, the head at
    the last position only."""
    blocks = (matmul_flops_per_token(m) - 2.0 * head_params(m)) * prompt_len
    return (blocks
            + attention_layers(m) * attention_flops(m, causal_pairs(prompt_len))
            + conv_layers(m) * conv_ops_bytes(m, prompt_len, 2)[0]
            + 2.0 * head_params(m))


def prefill_attn_ops_bytes(m: Dict[str, Any], s: int, elem_bytes: int
                           ) -> Tuple[float, float]:
    """ONE attention layer's prompt attention on one sequence of `s` rows
    (the kernel `flash_fwd` at a head of 64): (operations, bytes).
    Operations of the live causal pairs at 4 x head_dim a pair a head; bytes:
    q and the result once, k and v of the kv heads once (the program hands
    the kernel k and v repeated for the query heads, which the algorithm does
    not need)."""
    h, kvh, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  head_dim(m))
    byts = s * (2 * h + 2 * kvh) * hd * elem_bytes
    return attention_flops(m, causal_pairs(s)), float(byts)


def decode_attn_bytes(m: Dict[str, Any], kv_tokens: float, kv_bytes: int
                      ) -> float:
    """ONE attention layer's decode attention over `kv_tokens` cached
    positions summed over the slots and steps: the bytes of their keys and
    values (2 x kv heads x head_dim numbers a position: 2,048 B at the
    published widths in bfloat16, which is what the arena holds)."""
    return float(kv_tokens) * 2 * m["num_key_value_heads"] * head_dim(m) \
        * kv_bytes


def experts_ops_bytes(m: Dict[str, Any], assignments: float, touched: float,
                      weight_bytes: int, act_bytes: int) -> Tuple[float, float]:
    """The grouped matmuls of ONE sparse layer (the program's `experts`
    scope) over `assignments` rows (tokens x experts a token) that touch
    `touched` distinct experts: (operations, bytes). Bytes: each touched
    expert's three matrices once, each row read once and its result written
    once."""
    ops = 2.0 * expert_params(m) * assignments
    byts = (touched * expert_params(m) * weight_bytes
            + 2.0 * assignments * m["hidden_size"] * act_bytes)
    return ops, byts


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int, *, experts_touched: float
                          ) -> Tuple[float, float]:
    """One decode step over the live slots with the given context lengths:
    (operations, bytes). Bytes are what must cross HBM once: every weight
    outside the routed experts (the embedding once, as the head), the
    `experts_touched` distinct experts a sparse layer (from the program's
    counter), K and V of each slot's context in the ATTENTION layers, and
    each live slot's window of every conv layer in and out."""
    n = len(context_lens)
    dense, sparse = layers(m)
    la, lc = attention_layers(m), conv_layers(m)
    weights = (la * (attention_params(m) + 2 * head_dim(m))
               + lc * conv_params(m) + dense * dense_ffn_params(m)
               + sparse * (router_params(m)
                           + experts_touched * expert_params(m))
               + m["num_hidden_layers"] * 2 * m["hidden_size"]
               + head_params(m) + m["hidden_size"])
    live = float(sum(context_lens))
    ops = (n * (matmul_flops_per_token(m)
                + lc * conv_ops_bytes(m, 1, kv_bytes)[0])
           + la * attention_flops(m, live))
    byts = (float(weight_bytes) * weights
            + la * decode_attn_bytes(m, live, kv_bytes)
            + 2.0 * n * lc * conv_state_bytes(m, kv_bytes))
    return ops, byts
