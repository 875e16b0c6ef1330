"""Operations and bytes of `arch: keye`, by the rules at the top of
benchmark/flops.py: what the mathematics requires, a multiply-add is two
operations, causal attention at its lower triangle. What this block adds to
OLMoE's counts (benchmark/flops_olmoe.py):

* an expert's width is `moe_intermediate_size` (`intermediate_size` is the
  dense width, which no layer of this model has);
* a query at position t (0-based) attends to min(t + 1, topk) keys, not to
  t + 1: `selected_pairs`;
* the indexer scores EVERY causal pair, 2 * IH * (Id + 1) operations each
  (IH dot products of width Id, their relu and weighted sum), and projects
  IH * Id + Id + IH more columns of the normed input;
* a decode step reads the indexer key of every live position (Id elements)
  and K and V of the SELECTED positions only.

The bisection that finds the topk-th score is compares and counts, not
multiply-adds, and is counted nowhere: a selection kernel's share of its
roofline says how far its time is from the scoring alone.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops import head_params
from benchmark import flops_olmoe
from benchmark.flops_olmoe import attention_params, router_params  # noqa: F401


def _sa(m: Dict[str, Any]) -> Tuple[int, int, int]:
    sa = m["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def indexer_params(m: Dict[str, Any]) -> int:
    ih, idim, _ = _sa(m)
    return m["hidden_size"] * (ih * idim + idim + ih)


def expert_params(m: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def norm_params(m: Dict[str, Any]) -> int:
    """attn_norm, mlp_norm, the per-head q and k norms, the indexer key's
    LayerNorm (weight and bias)."""
    return 2 * m["hidden_size"] + 2 * m["head_dim"] + 2 * _sa(m)[1]


def active_layer_weights(m: Dict[str, Any]) -> int:
    """Weights one token multiplies in one block."""
    return (attention_params(m) + indexer_params(m) + router_params(m)
            + m["num_experts_per_tok"] * expert_params(m))


def layer_params(m: Dict[str, Any]) -> int:
    return (attention_params(m) + indexer_params(m) + router_params(m)
            + m["num_experts"] * expert_params(m) + norm_params(m))


def total_params(m: Dict[str, Any]) -> int:
    return (m["num_hidden_layers"] * layer_params(m) + 2 * head_params(m)
            + m["hidden_size"])


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    return 2.0 * (m["num_hidden_layers"] * active_layer_weights(m)
                  + head_params(m))


def causal_pairs(n: int) -> float:
    return n * (n + 1) / 2.0


def selected_pairs(m: Dict[str, Any], n: int) -> float:
    """(query, key) pairs attention keeps over a sequence of n positions:
    min(t + 1, topk) for t = 0..n-1."""
    k = min(_sa(m)[2], n)
    return causal_pairs(k) + (n - k) * float(k)


def attention_flops(m: Dict[str, Any], pairs: float) -> float:
    """QK^T and PV over `pairs` (query, key) pairs, one layer."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"] * pairs


def index_flops(m: Dict[str, Any], pairs: float) -> float:
    """The indexer's scores over `pairs` (query, key) pairs, one layer."""
    ih, idim, _ = _sa(m)
    return 2.0 * ih * (idim + 1) * pairs


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (3x forward), no recompute."""
    layers = m["num_hidden_layers"]
    attn = layers * (attention_flops(m, selected_pairs(m, seq))
                     + index_flops(m, causal_pairs(seq))) / seq
    return 3.0 * (matmul_flops_per_token(m) + attn)


def prefill_flops(m: Dict[str, Any], prompt_len: int) -> float:
    """One prompt's prefill: every position through the blocks, the head at
    the last position only."""
    layers = m["num_hidden_layers"]
    return (2.0 * layers * active_layer_weights(m) * prompt_len
            + layers * (attention_flops(m, selected_pairs(m, prompt_len))
                        + index_flops(m, causal_pairs(prompt_len)))
            + 2.0 * head_params(m))


def experts_ops_bytes(m: Dict[str, Any], assignments: float, touched: float,
                      weight_bytes: int, act_bytes: int) -> Tuple[float, float]:
    """The grouped matmuls of ONE layer over `assignments` rows that touch
    `touched` distinct experts: flops_olmoe's count at this model's expert
    width."""
    return flops_olmoe.experts_ops_bytes(
        dict(m, intermediate_size=m["moe_intermediate_size"]), assignments,
        touched, weight_bytes, act_bytes)


def index_select_ops_bytes(m: Dict[str, Any], n: int,
                           act_bytes: int) -> Tuple[float, float]:
    """ONE layer's scoring and selection over a sequence of n positions (the
    program's `index_select` kernel): (operations, bytes). Operations are
    the scores'; bytes the indexer's queries, keys and weights read once and
    one byte of selection written for every (query, key)."""
    ih, idim, _ = _sa(m)
    byts = n * (ih * idim + idim) * act_bytes + n * ih * 4 + float(n) * n
    return index_flops(m, causal_pairs(n)), byts


def sparse_decode_counts(m: Dict[str, Any], selected_keys: float,
                         live_keys: float, kv_bytes: int
                         ) -> Tuple[float, float]:
    """ONE layer's sparse attention in decode (the program's scopes
    `indexer`, `select`, `sparse_attn`) over queries that score `live_keys`
    positions and attend to `selected_keys` of them in all (the engine's
    counters of those names): (operations, bytes). Bytes: the indexer key of
    every live position, K and V of the selected ones."""
    kv_row = 2 * m["num_key_value_heads"] * m["head_dim"] * kv_bytes
    return (attention_flops(m, selected_keys) + index_flops(m, live_keys),
            kv_row * selected_keys + _sa(m)[1] * kv_bytes * live_keys)


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int, *, experts_touched: float
                          ) -> Tuple[float, float]:
    """One decode step over the live slots with the given context lengths:
    (operations, bytes). Bytes are what must cross HBM once: attention's,
    the indexer's, the router's and the head's weights, the weights of the
    `experts_touched` distinct experts a layer (from the program's counter),
    and what `sparse_decode_counts` reads of the caches."""
    layers = m["num_hidden_layers"]
    weights = (layers * (attention_params(m) + indexer_params(m)
                         + router_params(m) + norm_params(m)
                         + experts_touched * expert_params(m))
               + head_params(m) + m["hidden_size"])
    ops, byts = sparse_decode_counts(
        m, sum(min(c, _sa(m)[2]) for c in context_lens), sum(context_lens),
        kv_bytes)
    return (len(context_lens) * matmul_flops_per_token(m) + layers * ops,
            float(weight_bytes) * weights + layers * byts)
