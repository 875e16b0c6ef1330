"""Operations and bytes of `arch: mimo`, by the rules at the top of
benchmark/flops.py: what the mathematics requires, a multiply-add is two
operations, attention at its LIVE (query, key) pairs: the causal triangle in
a full layer, min(i + 1, window) keys a query in a window layer, never the
blocks a kernel touched nor the lanes a cache pads, so that no share can
pass 100. `m` holds the published keys as the configuration file has them:
`n_routed_experts` is the experts HELD here,
`expert_parallel.routed_experts_total` the router's width,
`hybrid_layer_pattern` and `moe_layer_freq` say each held layer's kind.

What this chip computes is counted, nothing an absent chip would: a token's
routed work here is its assignments to the HELD experts, `local` of them
(from the program's counters where a reader has them; in expectation
`num_experts_per_tok * n_routed_experts / routed_experts_total`, 0.5 at the
published sizes).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops import head_params


def kv_heads(m: Dict[str, Any], window: bool) -> int:
    return m["swa_num_key_value_heads"] if window \
        else m["num_key_value_heads"]


def attention_params(m: Dict[str, Any], window: bool) -> int:
    """Wq, Wk, Wv, Wo of a layer of one kind (and a window layer's sink)."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    dk, dv, kvh = m["head_dim"], m["v_head_dim"], kv_heads(m, window)
    return d * h * dk + d * kvh * (dk + dv) + h * dv * d + (h if window else 0)


def expert_params(m: Dict[str, Any]) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_ffn_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: Dict[str, Any]) -> int:
    return m["hidden_size"] * m["expert_parallel"]["routed_experts_total"]


def norm_params(m: Dict[str, Any]) -> int:
    return 2 * m["hidden_size"]


def layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(dense layers, sparse layers) held here."""
    sparse = sum(m["moe_layer_freq"])
    return m["num_hidden_layers"] - sparse, sparse


def attention_layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(full-attention layers, window layers) held here."""
    window = sum(m["hybrid_layer_pattern"])
    return m["num_hidden_layers"] - window, window


def expected_local(m: Dict[str, Any]) -> float:
    """Assignments a token a sparse layer that fall to experts held here,
    in expectation under even routing."""
    return m["num_experts_per_tok"] * m["n_routed_experts"] \
        / m["expert_parallel"]["routed_experts_total"]


def _attention_weights(m: Dict[str, Any]) -> int:
    full, window = attention_layers(m)
    return full * attention_params(m, False) \
        + window * attention_params(m, True)


def total_params(m: Dict[str, Any]) -> int:
    """Every parameter held on this chip."""
    dense, sparse = layers(m)
    total = m["expert_parallel"]["routed_experts_total"]
    return (_attention_weights(m) + dense * dense_ffn_params(m)
            + sparse * (router_params(m) + total        # the selection bias
                        + m["n_routed_experts"] * expert_params(m))
            + m["num_hidden_layers"] * norm_params(m)
            + 2 * head_params(m) + m["hidden_size"])


def matmul_flops_per_token(m: Dict[str, Any], local: float = None) -> float:
    """Forward matmul operations one token costs THIS chip, its `local`
    assignments a sparse layer through the held experts."""
    dense, sparse = layers(m)
    local = expected_local(m) if local is None else local
    _, window = attention_layers(m)
    return 2.0 * (
        _attention_weights(m) - window * m["num_attention_heads"]  # sinks
        + dense * dense_ffn_params(m)
        + sparse * (router_params(m) + local * expert_params(m))
        + head_params(m))


def causal_pairs(n: int) -> float:
    return n * (n + 1) / 2.0


def window_pairs(n: int, window: int) -> float:
    """Sum over queries i < n of min(i + 1, window)."""
    w = min(n, window)
    return causal_pairs(w) + (n - w) * float(window)


def attention_flops(m: Dict[str, Any], pairs: float) -> float:
    """One layer's attention over `pairs` live (query, key) pairs: scores
    over head_dim, values over v_head_dim, every query head."""
    return 2.0 * m["num_attention_heads"] * pairs * (
        m["head_dim"] + m["v_head_dim"])


def prefill_flops(m: Dict[str, Any], prompt_len: int,
                  local: float = None) -> float:
    """One prompt's prefill: every position through the blocks, the head at
    the last position only."""
    full, window = attention_layers(m)
    return ((matmul_flops_per_token(m, local) - 2.0 * head_params(m))
            * prompt_len
            + full * attention_flops(m, causal_pairs(prompt_len))
            + window * attention_flops(
                m, window_pairs(prompt_len, m["sliding_window"]))
            + 2.0 * head_params(m))


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("arch 'mimo' serves only")


# -- the four attention counts: one layer each --------------------------------

def prefill_attn_ops_bytes(m: Dict[str, Any], s: int, window: bool,
                           elem_bytes: int) -> Tuple[float, float]:
    """ONE layer's prompt attention on one sequence of `s` rows (the kernels
    `window_flash_fwd` / `full_flash_fwd`): (operations, bytes). Operations
    of the live pairs at 2 x (head_dim + v_head_dim) a pair a head; bytes: q
    and the result once, k and v of the kind's kv heads once."""
    h, dk, dv = m["num_attention_heads"], m["head_dim"], m["v_head_dim"]
    pairs = window_pairs(s, m["sliding_window"]) if window \
        else causal_pairs(s)
    byts = s * (h * (dk + dv) + kv_heads(m, window) * (dk + dv)) * elem_bytes
    return attention_flops(m, pairs), float(byts)


def decode_attn_bytes(m: Dict[str, Any], kv_tokens: float, window: bool,
                      kv_bytes: int) -> float:
    """ONE layer's decode attention over `kv_tokens` cached positions summed
    over the slots and steps (`live_kv_tokens` for a full layer,
    `window_kv_tokens` for a window layer): the bytes of their keys and
    values at the kind's kv heads, head_dim + v_head_dim numbers each (what
    the algorithm needs: the caches hold a 192-wide key in 256 lanes)."""
    return float(kv_tokens) * kv_heads(m, window) * (
        m["head_dim"] + m["v_head_dim"]) * kv_bytes


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
               + sparse * (router_params(m)
                           + experts_touched * expert_params(m))
               + m["num_hidden_layers"] * norm_params(m)
               + head_params(m) + m["hidden_size"])
    live = float(sum(context_lens))
    ring = float(sum(min(n, m["sliding_window"]) for n in context_lens))
    ops = (len(context_lens) * matmul_flops_per_token(m, local)
           + full * attention_flops(m, live)
           + window * attention_flops(m, ring))
    byts = (float(weight_bytes) * weights
            + full * decode_attn_bytes(m, live, False, kv_bytes)
            + window * decode_attn_bytes(m, ring, True, kv_bytes))
    return ops, byts
