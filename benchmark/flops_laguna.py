"""Operations and bytes of `arch: laguna`, by the rules at the top of
benchmark/flops.py: what the mathematics requires, a multiply-add is two
operations, attention at its LIVE (query, key) pairs: the causal triangle in
a full layer, min(i + 1, window) keys a query in a window layer, at the
KIND's query heads (48 and 72 published), never the blocks a kernel touched,
so that no share can pass 100. `m` holds the published keys as the
configuration file has them: `num_experts` is the experts HELD here,
`expert_parallel.routed_experts_total` the router's width, `layer_types`,
`mlp_layer_types` and `num_attention_heads_per_layer` say each layer's kind,
feed-forward and query heads, the held layers their first `num_hidden_layers`
entries.

What this chip computes is counted, nothing an absent chip would: a token's
routed work here is its assignments to the HELD experts, `local` of them
(from the program's counters where a reader has them; in expectation
`num_experts_per_tok * num_experts / routed_experts_total`, 1.25 at the
published sizes and a share of an eighth); the shared expert whole.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops import head_params

WINDOW = "sliding_attention"


def per_layer(m: Dict[str, Any], key: str) -> list:
    """A list with an entry a layer, at the layers held."""
    return list(m[key])[:m["num_hidden_layers"]]


def heads(m: Dict[str, Any], window: bool) -> int:
    """Query heads of a layer of one kind (one number a kind: the adapter
    refuses a list that says otherwise)."""
    for kind, n in zip(per_layer(m, "layer_types"),
                       per_layer(m, "num_attention_heads_per_layer")):
        if (kind == WINDOW) == window:
            return n
    return 0


def attention_params(m: Dict[str, Any], window: bool) -> int:
    """Wq and Wo at the kind's heads, Wk, Wv, and the gate's column a head."""
    d, h = m["hidden_size"], heads(m, window)
    dk, kvh = m["head_dim"], m["num_key_value_heads"]
    return 2 * d * h * dk + 2 * d * kvh * dk + d * h


def expert_params(m: Dict[str, Any]) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["shared_expert_intermediate_size"]


def dense_ffn_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: Dict[str, Any]) -> int:
    return m["hidden_size"] * m["expert_parallel"]["routed_experts_total"]


def norm_params(m: Dict[str, Any]) -> int:
    return 2 * m["hidden_size"]


def layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(dense layers, sparse layers) held here."""
    sparse = sum(t == "sparse" for t in per_layer(m, "mlp_layer_types"))
    return m["num_hidden_layers"] - sparse, sparse


def attention_layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(full-attention layers, window layers) held here."""
    window = sum(t == WINDOW for t in per_layer(m, "layer_types"))
    return m["num_hidden_layers"] - window, window


def expected_local(m: Dict[str, Any]) -> float:
    """Assignments a token a sparse layer that fall to experts held here,
    in expectation under even routing."""
    return m["num_experts_per_tok"] * m["num_experts"] \
        / m["expert_parallel"]["routed_experts_total"]


def _attention_weights(m: Dict[str, Any]) -> int:
    full, window = attention_layers(m)
    return full * attention_params(m, False) \
        + window * attention_params(m, True)


def total_params(m: Dict[str, Any]) -> int:
    """Every parameter held on this chip."""
    dense, sparse = layers(m)
    return (_attention_weights(m) + dense * dense_ffn_params(m)
            + sparse * (router_params(m) + shared_params(m)
                        + m["num_experts"] * expert_params(m))
            + m["num_hidden_layers"] * norm_params(m)
            + 2 * head_params(m) + m["hidden_size"])


def matmul_flops_per_token(m: Dict[str, Any], local: float = None) -> float:
    """Forward matmul operations one token costs THIS chip, its `local`
    assignments a sparse layer through the held experts."""
    dense, sparse = layers(m)
    local = expected_local(m) if local is None else local
    return 2.0 * (
        _attention_weights(m) + dense * dense_ffn_params(m)
        + sparse * (router_params(m) + shared_params(m)
                    + local * expert_params(m))
        + head_params(m))


def causal_pairs(n: int) -> float:
    return n * (n + 1) / 2.0


def window_pairs(n: int, window: int) -> float:
    """Sum over queries i < n of min(i + 1, window)."""
    w = min(n, window)
    return causal_pairs(w) + (n - w) * float(window)


def attention_flops(m: Dict[str, Any], pairs: float, window: bool) -> float:
    """One layer's attention over `pairs` live (query, key) pairs: scores and
    values over head_dim each, every query head of the kind."""
    return 4.0 * heads(m, window) * pairs * m["head_dim"]


def prefill_flops(m: Dict[str, Any], prompt_len: int,
                  local: float = None) -> float:
    """One prompt's prefill: every position through the blocks, the head at
    the last position only. `local`: the prompt's assignments to held
    experts summed over the sparse layers (the program's count), else their
    expectation."""
    full, window = attention_layers(m)
    if local is not None:
        local = local / (prompt_len * layers(m)[1])
    return ((matmul_flops_per_token(m, local) - 2.0 * head_params(m))
            * prompt_len
            + full * attention_flops(m, causal_pairs(prompt_len), False)
            + window * attention_flops(
                m, window_pairs(prompt_len, m["sliding_window"]), True)
            + 2.0 * head_params(m))


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("arch 'laguna' serves only")


# -- the four attention counts: one layer each --------------------------------

def prefill_attn_ops_bytes(m: Dict[str, Any], s: int, window: bool,
                           elem_bytes: int) -> Tuple[float, float]:
    """ONE layer's prompt attention on one sequence of `s` rows (the kernels
    `window_blocks_fwd` / `full_flash_fwd`): (operations, bytes). Operations
    of the live pairs at 4 x head_dim a pair a head of the kind; bytes: q and
    the result once at the kind's heads, k and v of the 8 kv heads once."""
    h, dk, kvh = heads(m, window), m["head_dim"], m["num_key_value_heads"]
    pairs = window_pairs(s, m["sliding_window"]) if window \
        else causal_pairs(s)
    byts = s * 2 * dk * (h + kvh) * elem_bytes
    return attention_flops(m, pairs, window), float(byts)


def decode_attn_bytes(m: Dict[str, Any], kv_tokens: float, window: bool,
                      kv_bytes: int) -> float:
    """ONE layer's decode attention over `kv_tokens` cached positions summed
    over the slots and steps (`live_kv_tokens` for a full layer,
    `window_kv_tokens` for a window layer): the bytes of their keys and
    values, 8 kv heads of 2 x head_dim numbers each, in either kind."""
    return float(kv_tokens) * m["num_key_value_heads"] * 2 * m["head_dim"] \
        * kv_bytes


def experts_ops_bytes(m: Dict[str, Any], assignments: float, touched: float,
                      weight_bytes: int, act_bytes: int) -> Tuple[float, float]:
    """The grouped matmuls of ONE layer (the program's `experts` scope) over
    `assignments` LOCAL rows that touch `touched` distinct HELD experts:
    (operations, bytes). Bytes: each touched expert's three matrices once,
    each row read once and its result written once."""
    ops = 2.0 * expert_params(m) * assignments
    byts = (touched * expert_params(m) * weight_bytes
            + 2.0 * assignments * m["hidden_size"] * act_bytes)
    return ops, byts


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int, *, experts_touched: float,
                          local: float = None) -> Tuple[float, float]:
    """One decode step over the live slots with the given context lengths:
    (operations, bytes). Bytes are what must cross HBM once: every weight
    outside the routed experts, the `experts_touched` distinct held experts a
    sparse layer (from the program's counter), each slot's cached rows in the
    full layers and its window's in the window layers."""
    dense, sparse = layers(m)
    full, window = attention_layers(m)
    weights = (_attention_weights(m) + dense * dense_ffn_params(m)
               + sparse * (router_params(m) + shared_params(m)
                           + experts_touched * expert_params(m))
               + m["num_hidden_layers"] * norm_params(m)
               + head_params(m) + m["hidden_size"])
    live = float(sum(context_lens))
    ring = float(sum(min(n, m["sliding_window"]) for n in context_lens))
    ops = (len(context_lens) * matmul_flops_per_token(m, local)
           + full * attention_flops(m, live, False)
           + window * attention_flops(m, ring, True))
    byts = (float(weight_bytes) * weights
            + full * decode_attn_bytes(m, live, False, kv_bytes)
            + window * decode_attn_bytes(m, ring, True, kv_bytes))
    return ops, byts
