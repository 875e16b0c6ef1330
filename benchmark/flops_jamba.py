"""Operations and bytes of `arch: jamba`, by the rules at the top of
benchmark/flops.py: what the mathematics requires, a multiply-add is two
operations, causal attention at its lower triangle. What a hybrid stack
changes: `num_hidden_layers` is BOTH kinds of layer; only the attention
layers (`attention_layers`) have q, k, v, o and keep K and V; every other
layer is a Mamba-1 mixer, whose matrices a token multiplies like any other
and whose recurrence is vector work on `Di x N` states a row:

  s = exp(dt (x) A) * s + (dt * x) (x) B;  y = s . C        9 operations an
  (row, channel, state) element: dt*A, the exponential counted as ONE, the
  decay's multiply, (dt*x)*B (dt*x is a row's, not an element's), the add,
  s*C and its add, and the two of the D term and the gate shared over N

(so 9 is an upper count of about 8.2; the peaks table has no vector or
transcendental peak, and a share against the matrix peak reads low: it is the
yardstick all the same). Every layer has the dense feed-forward; the head is
the embedding, counted once as parameters and once as a matmul.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops import attention_flops, head_dim, head_params


def attention_layer_indices(m: Dict[str, Any]) -> Tuple[int, ...]:
    """The layer pattern, which `config.json` gives by two keys."""
    return tuple(i for i in range(m["num_hidden_layers"])
                 if i % m["attn_layer_period"] == m["attn_layer_offset"])


def attention_layers(m: Dict[str, Any]) -> int:
    return len(attention_layer_indices(m))


def mamba_layers(m: Dict[str, Any]) -> int:
    return m["num_hidden_layers"] - attention_layers(m)


def inner(m: Dict[str, Any]) -> int:
    return m["mamba_expand"] * m["hidden_size"]


def mlp_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def attention_params(m: Dict[str, Any]) -> int:
    d, hd = m["hidden_size"], head_dim(m)
    return 2 * d * hd * (m["num_attention_heads"] + m["num_key_value_heads"])


def mixer_matmul_params(m: Dict[str, Any]) -> int:
    """The matrices of one Mamba mixer: in, x, dt and out projections."""
    d, di = m["hidden_size"], inner(m)
    r, n = m["mamba_dt_rank"], m["mamba_d_state"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def mixer_vector_params(m: Dict[str, Any]) -> int:
    """Convolution and its bias, the time step's bias, A_log, D, and the three
    norms of dt, B and C."""
    di, r, n = inner(m), m["mamba_dt_rank"], m["mamba_d_state"]
    return di * (m["mamba_d_conv"] + 1 + 1 + n + 1) + r + 2 * n


def total_params(m: Dict[str, Any]) -> int:
    la, lm, d = attention_layers(m), mamba_layers(m), m["hidden_size"]
    return (la * (attention_params(m) + mlp_params(m) + 2 * d)
            + lm * (mixer_matmul_params(m) + mixer_vector_params(m)
                    + mlp_params(m) + 2 * d)
            + head_params(m) + d)


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    la, lm = attention_layers(m), mamba_layers(m)
    return 2.0 * (la * attention_params(m) + lm * mixer_matmul_params(m)
                  + (la + lm) * mlp_params(m) + head_params(m))


def scan_flops(m: Dict[str, Any], rows: float) -> float:
    """ONE layer's recurrence over `rows` rows."""
    return 9.0 * rows * inner(m) * m["mamba_d_state"]


def conv_flops(m: Dict[str, Any], rows: float) -> float:
    return 2.0 * m["mamba_d_conv"] * rows * inner(m)


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (3x forward), no recompute; the program has no
    Train path over state-space layers yet, the count is the mathematics'."""
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
    """ONE layer's `selective_scan` kernel over `rows` rows: (operations,
    bytes). Bytes are the kernel's arguments and results once each: x, z and
    y in the activation dtype and dt in float32 a (row, channel); B and C in
    float32 a (row, state); A, the state in and the state out in float32 a
    (channel, state); D."""
    di, n = inner(m), m["mamba_d_state"]
    byts = rows * di * (3 * act_bytes + 4) + rows * 2 * n * 4 \
        + 3 * di * n * 4 + di * 4
    return scan_flops(m, rows), float(byts)


def slot_state_bytes(m: Dict[str, Any], act_bytes: int) -> int:
    """ONE slot's recurrent state in ONE layer: the float32 states and the
    convolution's window of K - 1 inputs."""
    return inner(m) * (m["mamba_d_state"] * 4
                       + (m["mamba_d_conv"] - 1) * act_bytes)


def decode_state_bytes(m: Dict[str, Any], slot_steps: float, act_bytes: int
                       ) -> float:
    """The recurrent state `slot_steps` (active slots x steps) decode steps
    read and write, all the state-space layers: each reads a slot's state of
    every layer once and writes it once."""
    return 2.0 * slot_steps * mamba_layers(m) * slot_state_bytes(m, act_bytes)


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int) -> Tuple[float, float]:
    """One decode step over the live slots with the given context lengths:
    (operations, bytes). Bytes are what must cross HBM once: every weight
    (the embedding once, as the head), K and V of each slot's context in the
    attention layers, and each live slot's recurrent state in and out."""
    n, la, lm = len(context_lens), attention_layers(m), mamba_layers(m)
    ops = n * (matmul_flops_per_token(m)
               + lm * (scan_flops(m, 1) + conv_flops(m, 1)))
    kv_row = 2 * m["num_key_value_heads"] * head_dim(m) * kv_bytes
    byts = float(weight_bytes) * total_params(m) \
        + decode_state_bytes(m, n, kv_bytes)
    for c in context_lens:
        ops += la * attention_flops(m, 1, c, False)
        byts += la * kv_row * c
    return ops, byts
