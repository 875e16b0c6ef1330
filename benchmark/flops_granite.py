"""Operations and bytes of `arch: granitemoehybrid` (Granite-4.0-H-Small),
by the rules at the top of benchmark/flops.py: what the mathematics requires,
a multiply-add is two operations, causal attention at its lower triangle.
What this stack changes: `num_hidden_layers` counts BOTH kinds of layer
(`layer_types`): only the "attention" layers have q, k, v, o and keep K and
V; every "mamba" layer is a Mamba-2 mixer (one input projection to the gate,
x, B, C and a time step a head; a convolution over x, B and C; the
recurrence; a gated norm; the output projection) and keeps a state of
`mamba_d_state x Di` float32 numbers a slot. EVERY layer has a router over
`expert_parallel.routed_experts_total` experts (`num_experts_per_tok` a
token), of which `num_local_experts` are HELD here, each `intermediate_size`
wide, and a shared expert of `shared_intermediate_size`. The head is the
embedding, counted once as parameters and once as a matmul.

The recurrence over a prompt is counted as the chunked dual form at the
published `mamba_chunk_size` Q, whatever implements it: a row's share of `C
B^T` (2 Q N), of `(G * L) (dt x)` (2 Q Di, and 3 Q H for the decays L), of `C
S_prev` and of `B^T (dt x)` (2 N Di each): 8.5 M operations a row a layer at
the published widths, where the row-by-row form takes 9 N Di = 9.4 M vector
operations. A decode step's update is that row-by-row form.

Bytes of the experts are those of the experts TOUCHED, and operations those
of the LOCAL assignments, from the program's counters, never by assumption.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from benchmark.flops import attention_flops, head_dim, head_params


def layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(dense, sparse): every layer held has the router and the experts."""
    return 0, m["num_hidden_layers"]


def attention_layers(m: Dict[str, Any]) -> int:
    return sum(1 for kind in m["layer_types"][:m["num_hidden_layers"]]
               if kind == "attention")


def mamba_layers(m: Dict[str, Any]) -> int:
    return m["num_hidden_layers"] - attention_layers(m)


def inner(m: Dict[str, Any]) -> int:
    return m["mamba_n_heads"] * m["mamba_d_head"]


def conv_channels(m: Dict[str, Any]) -> int:
    """x, B and C go through the convolution together."""
    return inner(m) + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def routed_total(m: Dict[str, Any]) -> int:
    """The router's width: the experts of the whole layer."""
    ep = m.get("expert_parallel")
    return ep["routed_experts_total"] if ep else m["num_local_experts"]


def expected_local(m: Dict[str, Any]) -> float:
    """Assignments a token a layer that fall to experts held here, in
    expectation under even routing."""
    return m["num_experts_per_tok"] * m["num_local_experts"] / routed_total(m)


def attention_params(m: Dict[str, Any]) -> int:
    d, hd = m["hidden_size"], head_dim(m)
    return 2 * d * hd * (m["num_attention_heads"] + m["num_key_value_heads"])


def mixer_matmul_params(m: Dict[str, Any]) -> int:
    """The two projections of one Mamba-2 mixer."""
    d, di = m["hidden_size"], inner(m)
    return d * (di + conv_channels(m) + m["mamba_n_heads"]) + di * d


def mixer_vector_params(m: Dict[str, Any]) -> int:
    """The convolution's taps and bias, a head's dt_bias, A_log and D, the
    gated norm's weight."""
    return (conv_channels(m) * (m["mamba_d_conv"] + 1)
            + 3 * m["mamba_n_heads"] + inner(m))


def expert_params(m: Dict[str, Any]) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def shared_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["shared_intermediate_size"]


def router_params(m: Dict[str, Any]) -> int:
    return m["hidden_size"] * routed_total(m)


def _outside_experts(m: Dict[str, Any]) -> int:
    """Every weight but the routed experts' (the embedding once)."""
    la, lm, d = attention_layers(m), mamba_layers(m), m["hidden_size"]
    return (la * attention_params(m)
            + lm * (mixer_matmul_params(m) + mixer_vector_params(m))
            + (la + lm) * (router_params(m) + shared_params(m) + 2 * d)
            + head_params(m) + d)


def total_params(m: Dict[str, Any]) -> int:
    return _outside_experts(m) + m["num_hidden_layers"] \
        * m["num_local_experts"] * expert_params(m)


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    """Forward matmul operations a token: the projections, the router, the
    shared expert, the assignments to held experts a layer (their expectation
    under even routing), the head."""
    la, lm = attention_layers(m), mamba_layers(m)
    return 2.0 * (la * attention_params(m) + lm * mixer_matmul_params(m)
                  + (la + lm) * (router_params(m) + shared_params(m)
                                 + expected_local(m) * expert_params(m))
                  + head_params(m))


def scan_flops(m: Dict[str, Any], rows: float) -> float:
    """ONE layer's recurrence over a prompt of `rows` rows, as the chunked
    dual form at `mamba_chunk_size` (the top of this file)."""
    di, n, h = inner(m), m["mamba_d_state"], m["mamba_n_heads"]
    q = m["mamba_chunk_size"]
    return rows * (4.0 * n * di + 2.0 * q * di + 2.0 * q * n + 3.0 * q * h)


def step_flops(m: Dict[str, Any], rows: float) -> float:
    """ONE layer's one-token update of `rows` slots: 9 operations an element
    of state, as benchmark/flops_jamba.py counts them."""
    return 9.0 * rows * inner(m) * m["mamba_d_state"]


def conv_flops(m: Dict[str, Any], rows: float) -> float:
    return 2.0 * m["mamba_d_conv"] * rows * conv_channels(m)


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (3x forward), no recompute; the program has no
    Train path over state-space layers, the count is the mathematics'."""
    per_row = (attention_layers(m) * attention_flops(m, seq, seq, True) / seq
               + mamba_layers(m) * (scan_flops(m, 1) + conv_flops(m, 1)))
    return 3.0 * (matmul_flops_per_token(m) + per_row)


def prefill_flops(m: Dict[str, Any], prompt_len: int) -> float:
    """One prompt's prefill: every position through the blocks, the head at
    the last position only."""
    blocks = (matmul_flops_per_token(m) - 2.0 * head_params(m)) * prompt_len
    return (blocks
            + attention_layers(m) * attention_flops(m, prompt_len, prompt_len,
                                                    True)
            + mamba_layers(m) * (scan_flops(m, prompt_len)
                                 + conv_flops(m, prompt_len))
            + 2.0 * head_params(m))


def selective_scan_ops_bytes(m: Dict[str, Any], rows: int, act_bytes: int
                             ) -> Tuple[float, float]:
    """ONE layer's recurrence over a prompt of `rows` rows (the program's
    `scan` scope of `jit_prefill`): (operations, bytes). Bytes are its
    arguments and results once each: x and y in the activation dtype a (row,
    channel); dt in float32 a (row, head); B and C in the activation dtype a
    (row, state); the state in and the state out in float32; A and D."""
    di, n, h = inner(m), m["mamba_d_state"], m["mamba_n_heads"]
    byts = rows * (2 * di * act_bytes + h * 4 + 2 * n * act_bytes) \
        + 2 * di * n * 4 + 2 * h * 4
    return scan_flops(m, rows), float(byts)


def slot_state_bytes(m: Dict[str, Any], act_bytes: int) -> int:
    """ONE slot's recurrent state in ONE layer: the float32 states and the
    convolution's window of K - 1 inputs of x, B and C."""
    return inner(m) * m["mamba_d_state"] * 4 \
        + (m["mamba_d_conv"] - 1) * conv_channels(m) * act_bytes


def decode_state_bytes(m: Dict[str, Any], slot_steps: float, act_bytes: int
                       ) -> float:
    """The recurrent state `slot_steps` (active slots x steps) decode steps
    read and write, all the state-space layers: each reads a slot's state of
    every layer once and writes it once."""
    return 2.0 * slot_steps * mamba_layers(m) * slot_state_bytes(m, act_bytes)


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
                          kv_bytes: int, *,
                          experts_touched: Optional[float] = None
                          ) -> Tuple[float, float]:
    """One decode step over the live slots with the given context lengths:
    (operations, bytes). Bytes are what must cross HBM once: every weight
    outside the routed experts (the embedding once, as the head), the
    `experts_touched` distinct held experts a layer (the program's counter;
    every held expert if None), K and V of each slot's context in the
    ATTENTION layers, and each live slot's recurrent state of every
    state-space layer in and out."""
    n, la, lm = len(context_lens), attention_layers(m), mamba_layers(m)
    touched = m["num_local_experts"] if experts_touched is None \
        else experts_touched
    weights = _outside_experts(m) \
        + m["num_hidden_layers"] * touched * expert_params(m)
    live = float(sum(context_lens))
    ops = (n * (matmul_flops_per_token(m)
                + lm * (step_flops(m, 1) + conv_flops(m, 1)))
           + la * 4.0 * m["num_attention_heads"] * head_dim(m) * live)
    kv_row = 2 * m["num_key_value_heads"] * head_dim(m) * kv_bytes
    byts = (float(weight_bytes) * weights + la * kv_row * live
            + decode_state_bytes(m, n, kv_bytes))
    return ops, byts
