"""Operations and bytes of `arch: dots`, by the rules at the top of
benchmark/flops.py: what the mathematics requires, a multiply-add is two
operations, causal attention at its lower triangle. `m` holds the published
keys as the configuration file has them: `n_routed_experts` is the experts
HELD here, `expert_parallel.routed_experts_total` the router's width,
`first_k_dense_replace` of the `num_hidden_layers` layers are dense.

What this chip computes is counted, nothing an absent chip would: a token's
routed work here is its assignments to the HELD experts, `local` of them
(from the program's counters where a reader has them; in expectation
`num_experts_per_tok * n_routed_experts / routed_experts_total`, 0.5 at the
published sizes).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops import head_params


def attention_params(m: Dict[str, Any]) -> int:
    """MLA's five matrices: W_DQ, W_UQ, W_DKV, W_UKV, W_O."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    rq, rkv = m["q_lora_rank"], m["kv_lora_rank"]
    return (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * d)


def expert_params(m: Dict[str, Any]) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: Dict[str, Any]) -> int:
    return m["n_shared_experts"] * expert_params(m)


def dense_ffn_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: Dict[str, Any]) -> int:
    return m["hidden_size"] * m["expert_parallel"]["routed_experts_total"]


def norm_params(m: Dict[str, Any]) -> int:
    """attn_norm, mlp_norm, the q latent's and the kv latent's norms."""
    return 2 * m["hidden_size"] + m["q_lora_rank"] + m["kv_lora_rank"]


def layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(dense layers, sparse layers) held here."""
    dense = m["first_k_dense_replace"]
    return dense, m["num_hidden_layers"] - dense


def expected_local(m: Dict[str, Any]) -> float:
    """Assignments a token a sparse layer that fall to experts held here,
    in expectation under even routing."""
    return m["num_experts_per_tok"] * m["n_routed_experts"] \
        / m["expert_parallel"]["routed_experts_total"]


def total_params(m: Dict[str, Any]) -> int:
    """Every parameter held on this chip."""
    dense, sparse = layers(m)
    per_sparse = (attention_params(m) + router_params(m) + shared_params(m)
                  + m["n_routed_experts"] * expert_params(m) + norm_params(m)
                  + m["expert_parallel"]["routed_experts_total"])   # the bias
    per_dense = attention_params(m) + dense_ffn_params(m) + norm_params(m)
    return (dense * per_dense + sparse * per_sparse + 2 * head_params(m)
            + m["hidden_size"])


def matmul_flops_per_token(m: Dict[str, Any], local: float = None) -> float:
    """Forward matmul operations one token costs THIS chip, its `local`
    assignments a sparse layer through the held experts."""
    dense, sparse = layers(m)
    local = expected_local(m) if local is None else local
    return 2.0 * (
        dense * (attention_params(m) + dense_ffn_params(m))
        + sparse * (attention_params(m) + router_params(m) + shared_params(m)
                    + local * expert_params(m))
        + head_params(m))


def attention_flops(m: Dict[str, Any], pairs: float) -> float:
    """One layer's attention in its NAIVE form over `pairs` (query, key)
    pairs: scores over 128 + 64, values over 128, every head."""
    return 2.0 * m["num_attention_heads"] * pairs * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])


def causal_pairs(n: int) -> float:
    return n * (n + 1) / 2.0


def prefill_flops(m: Dict[str, Any], prompt_len: int,
                  local: float = None) -> float:
    """One prompt's prefill: every position through the blocks, the head at
    the last position only."""
    return ((matmul_flops_per_token(m, local) - 2.0 * head_params(m))
            * prompt_len
            + m["num_hidden_layers"] * attention_flops(
                m, causal_pairs(prompt_len))
            + 2.0 * head_params(m))


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("arch 'dots' serves only")


def latent_flash_call_ops_bytes(heads: int, s: int, dn: int, dr: int,
                                dv: int, elem_bytes: int
                                ) -> Tuple[float, float]:
    """One call of the prompt's attention kernel (`latent_flash_fwd`) on one
    sequence of `s` positions: (operations, bytes). Causal; scores over dn +
    dr, values over dv. Bytes: each operand read and the result written once,
    the shared rotary key ONCE for all heads."""
    ops = 2.0 * heads * causal_pairs(s) * (dn + dr + dv)
    byts = (heads * s * (dn + dr) + heads * s * dn + s * dr
            + 2 * heads * s * dv) * elem_bytes
    return ops, float(byts)


def latent_decode_ops_bytes(m: Dict[str, Any], context_lens,
                            kv_bytes: int) -> Tuple[float, float]:
    """ONE layer's decode attention in the absorbed form (the program's
    kernel `paged_latent_decode`) for slots that read `context_lens`
    positions: (operations, bytes). A position is one row of rkv + dr
    numbers; every head scores it over all of them and sums it over the
    first rkv. Bytes: each live row once, each slot's query in and output
    out."""
    h, rkv, dr = (m["num_attention_heads"], m["kv_lora_rank"],
                  m["qk_rope_head_dim"])
    rows = float(sum(context_lens))
    ops = 2.0 * h * (2 * rkv + dr) * rows
    byts = (rkv + dr) * kv_bytes * rows + len(context_lens) * h * (
        (rkv + dr) * kv_bytes + rkv * 4)
    return ops, byts


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
    sparse layer (from the program's counter), and each slot's cached rows."""
    dense, sparse = layers(m)
    weights = (dense * (attention_params(m) + dense_ffn_params(m))
               + sparse * (attention_params(m) + router_params(m)
                           + shared_params(m)
                           + experts_touched * expert_params(m))
               + m["num_hidden_layers"] * norm_params(m)
               + head_params(m) + m["hidden_size"])
    ops, byts = latent_decode_ops_bytes(m, context_lens, kv_bytes)
    n_layers = m["num_hidden_layers"]
    return (len(context_lens) * matmul_flops_per_token(m, local)
            + n_layers * ops, float(weight_bytes) * weights + n_layers * byts)
