"""Operations and bytes of `arch: nemotron_h` (NVIDIA-Nemotron-3-Nano-30B-A3B),
by the rules at the top of benchmark/flops.py: what the mathematics requires,
a multiply-add is two operations, causal attention at its lower triangle.
What this stack changes: a layer is ONE part, by its letter in
`hybrid_override_pattern` (cut to `num_hidden_layers`): "M" a Mamba-2 mixer
(one input projection to the gate, x, the `n_groups` groups' B and C and a
time step a head; a convolution over x, B and C; the recurrence; a gated norm
a group; the output projection) that keeps a state of `ssm_state_size x Di`
float32 numbers a slot, Di = `mamba_num_heads x mamba_head_dim`; "E" a router
over `expert_parallel.routed_experts_total` experts (`num_experts_per_tok` a
token), of which `n_routed_experts` are HELD here, each of TWO matrices of
`moe_intermediate_size` (relu^2, no gate), and a shared expert of
`moe_shared_expert_intermediate_size`, two matrices too; "*" q, k, v, o with
`head_dim` a head (heads x head_dim is not the hidden size) that keeps K and
V. The head is untied: the embedding is a lookup and multiplies nothing.

The recurrence over a prompt is counted as the LEAST work that computes it,
whatever implements it: row by row, an element of state a row takes a decay,
an input (two multiplies and an add) and its part of y (a multiply and an
add), 5 N Di operations a row a layer, and a head's exponential. The program's
chunked dual form (`ops/ssm.py::ssd_scan`) makes more products than that (4 N
Di + 2 Q Di + 2 Q G N a row at chunks of Q) and moves them to the matrix
unit; its extra products are not work the mathematics asks for, so
`scan_roofline_pct` can only under-read. A decode step's update is the same
row.

Bytes of the experts are those of the experts TOUCHED, and operations those
of the LOCAL assignments, from the program's counters, never by assumption.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from benchmark.flops import attention_flops, head_dim, head_params


def pattern(m: Dict[str, Any]) -> str:
    return m["hybrid_override_pattern"][:m["num_hidden_layers"]]


def layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(dense, sparse): the E layers have the router and the experts, and no
    other layer has a feed-forward at all."""
    return 0, pattern(m).count("E")


def attention_layers(m: Dict[str, Any]) -> int:
    return pattern(m).count("*")


def mamba_layers(m: Dict[str, Any]) -> int:
    return pattern(m).count("M")


def inner(m: Dict[str, Any]) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def conv_channels(m: Dict[str, Any]) -> int:
    """x and every group's B and C go through the convolution together."""
    return inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def routed_total(m: Dict[str, Any]) -> int:
    """The router's width: the experts of the whole layer."""
    ep = m.get("expert_parallel")
    return ep["routed_experts_total"] if ep else m["n_routed_experts"]


def expected_local(m: Dict[str, Any]) -> float:
    """Assignments a token a layer that fall to experts held here, in
    expectation under even routing."""
    return m["num_experts_per_tok"] * m["n_routed_experts"] / routed_total(m)


def attention_params(m: Dict[str, Any]) -> int:
    d, hd = m["hidden_size"], head_dim(m)
    return 2 * d * hd * (m["num_attention_heads"] + m["num_key_value_heads"])


def mixer_matmul_params(m: Dict[str, Any]) -> int:
    """The two projections of one Mamba-2 mixer."""
    d, di = m["hidden_size"], inner(m)
    return d * (di + conv_channels(m) + m["mamba_num_heads"]) + di * d


def mixer_vector_params(m: Dict[str, Any]) -> int:
    """The convolution's taps and bias, a head's dt_bias, A_log and D, the
    gated norm's weight."""
    return (conv_channels(m) * (m["conv_kernel"] + 1)
            + 3 * m["mamba_num_heads"] + inner(m))


def expert_params(m: Dict[str, Any]) -> int:
    """One routed expert: up and down, no gate."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: Dict[str, Any]) -> int:
    return 2 * m["hidden_size"] * m["moe_shared_expert_intermediate_size"]


def router_params(m: Dict[str, Any]) -> int:
    """The router's matrix; its selection bias is `routed_total` more."""
    return m["hidden_size"] * routed_total(m)


def layer_params(m: Dict[str, Any], part: str,
                 held: Optional[int] = None) -> int:
    """Every weight of ONE layer of the kind `part` (its norm included), an E
    layer with `held` experts (None: those the configuration holds)."""
    d = m["hidden_size"]
    if part == "M":
        return d + mixer_matmul_params(m) + mixer_vector_params(m)
    if part == "*":
        return d + attention_params(m)
    held = m["n_routed_experts"] if held is None else held
    return (d + router_params(m) + routed_total(m) + shared_params(m)
            + held * expert_params(m))


def _outside_experts(m: Dict[str, Any]) -> int:
    """Every weight a step reads but the routed experts' (the head once; the
    embedding is a lookup)."""
    return (sum(layer_params(m, part, 0) for part in pattern(m))
            + head_params(m) + m["hidden_size"])


def total_params(m: Dict[str, Any]) -> int:
    """Every weight held: the layers, the final norm, embedding and head."""
    return (sum(layer_params(m, part) for part in pattern(m))
            + m["hidden_size"] + 2 * head_params(m))


def matmul_flops_per_token(m: Dict[str, Any],
                           local: Optional[float] = None) -> float:
    """Forward matmul operations a token: the projections, the router, the
    shared expert, `local` assignments to held experts an E layer (None:
    their expectation under even routing), the head."""
    la, lm, le = attention_layers(m), mamba_layers(m), layers(m)[1]
    local = expected_local(m) if local is None else local
    return 2.0 * (la * attention_params(m) + lm * mixer_matmul_params(m)
                  + le * (router_params(m) + shared_params(m)
                          + local * expert_params(m))
                  + head_params(m))


def scan_flops(m: Dict[str, Any], rows: float) -> float:
    """ONE layer's recurrence over `rows` rows, the least that computes it
    (the top of this file): 5 operations an element of state a row, and a
    head's exponential, its product with dt and A and its softplus."""
    return rows * (5.0 * inner(m) * m["ssm_state_size"]
                   + 3.0 * m["mamba_num_heads"])


def step_flops(m: Dict[str, Any], rows: float) -> float:
    """ONE layer's one-token update of `rows` slots: the same row."""
    return scan_flops(m, rows)


def conv_flops(m: Dict[str, Any], rows: float) -> float:
    return 2.0 * m["conv_kernel"] * rows * conv_channels(m)


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (3x forward), no recompute; the program has no
    Train path over state-space layers, the count is the mathematics'."""
    per_row = (attention_layers(m) * attention_flops(m, seq, seq, True) / seq
               + mamba_layers(m) * (scan_flops(m, 1) + conv_flops(m, 1)))
    return 3.0 * (matmul_flops_per_token(m) + per_row)


def prefill_flops(m: Dict[str, Any], prompt_len: int,
                  local: Optional[float] = None) -> float:
    """One prompt's prefill: every position through the blocks, the head at
    the last position only. `local`: the assignments to held experts the
    program counted for this prompt, summed over the E layers (None: their
    expectation under even routing)."""
    if local is not None:
        local = local / (layers(m)[1] * prompt_len)
    blocks = (matmul_flops_per_token(m, local) - 2.0 * head_params(m)) \
        * prompt_len
    return (blocks
            + attention_layers(m) * attention_flops(m, prompt_len, prompt_len,
                                                    True)
            + mamba_layers(m) * (scan_flops(m, prompt_len)
                                 + conv_flops(m, prompt_len))
            + 2.0 * head_params(m))


def selective_scan_ops_bytes(m: Dict[str, Any], rows: int, act_bytes: int
                             ) -> Tuple[float, float]:
    """ONE layer's recurrence over a prompt of `rows` rows (the program's
    `scan` scope of `jit_prefill`), the least work whatever implements it:
    (operations, bytes). Bytes are its arguments and results once each: x
    and y in the activation dtype a (row, channel); dt in float32 a (row,
    head); every group's B and C in the activation dtype a (row, state); the
    state in and the state out in float32; A and D."""
    di, n, h = inner(m), m["ssm_state_size"], m["mamba_num_heads"]
    byts = rows * (2 * di * act_bytes + h * 4
                   + 2 * m["n_groups"] * n * act_bytes) \
        + 2 * di * n * 4 + 2 * h * 4
    return scan_flops(m, rows), float(byts)


def slot_state_bytes(m: Dict[str, Any], act_bytes: int) -> int:
    """ONE slot's recurrent state in ONE layer: the float32 states (which do
    not depend on the groups) and the convolution's window of K - 1 inputs
    of x, B and C."""
    return inner(m) * m["ssm_state_size"] * 4 \
        + (m["conv_kernel"] - 1) * conv_channels(m) * act_bytes


def decode_state_bytes(m: Dict[str, Any], slot_steps: float, act_bytes: int
                       ) -> float:
    """The recurrent state `slot_steps` (active slots x steps) decode steps
    read and write, all the state-space layers: each reads a slot's state of
    every layer once and writes it once."""
    return 2.0 * slot_steps * mamba_layers(m) * slot_state_bytes(m, act_bytes)


def experts_ops_bytes(m: Dict[str, Any], assignments: float, touched: float,
                      weight_bytes: int, act_bytes: int) -> Tuple[float, float]:
    """The grouped matmuls of ONE layer (the program's `experts` scope), TWO
    an expert, over `assignments` LOCAL rows that touch `touched` distinct
    HELD experts: (operations, bytes). Bytes: each touched expert's two
    matrices once, each row read once and its result written once."""
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
    outside the routed experts (the head once; the embedding is a lookup of a
    row a slot), the `experts_touched` distinct held experts an E layer (the
    program's counter; every held expert if None), K and V of each slot's
    context in the ATTENTION layers, and each live slot's recurrent state of
    every state-space layer in and out."""
    n, la, lm = len(context_lens), attention_layers(m), mamba_layers(m)
    touched = m["n_routed_experts"] if experts_touched is None \
        else experts_touched
    weights = _outside_experts(m) + layers(m)[1] * touched * expert_params(m)
    live = float(sum(context_lens))
    ops = (n * (matmul_flops_per_token(m)
                + lm * (step_flops(m, 1) + conv_flops(m, 1)))
           + la * 4.0 * m["num_attention_heads"] * head_dim(m) * live)
    kv_row = 2 * m["num_key_value_heads"] * head_dim(m) * kv_bytes
    byts = (float(weight_bytes) * weights + la * kv_row * live
            + decode_state_bytes(m, n, kv_bytes))
    return ops, byts
