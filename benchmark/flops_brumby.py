"""Operations and bytes of `arch: brumby` (Brumby-14B-Base: a Qwen3-14B dense
decoder whose attention is power retention of degree 2), by the rules at the
top of `flops.py`: what the mathematics requires, a multiply-add two
operations, from shapes alone. `m` holds the published `config.json` keys.

The state is counted at the LEAST the equations need: `d (d + 1) / 2` rows
(8,256 at d = 128) of d values and one normaliser a kv head, float32,
whatever layout the program pads to (its 65 blocks of 128 lanes are 8,320:
0.78% more bytes than are counted here, so a share of the roofline can only
under-read).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

STATE_BYTES = 4     # the state is float32 (the configuration file, assumed)


def head_dim(m: Dict[str, Any]) -> int:
    return int(m.get("head_dim")
               or m["hidden_size"] // m["num_attention_heads"])


def state_rows(m: Dict[str, Any]) -> int:
    """Distinct products u_a u_b (a <= b) of a head: the expansion's width."""
    d = head_dim(m)
    return d * (d + 1) // 2


def layer_params(m: Dict[str, Any]) -> int:
    """Weights of one block: q, k, v, o, the gate's projection (one output a
    kv head), the q/k norms, the SwiGLU, the two norms. (`b_g` is a constant
    of the initialisation, not a weight.)"""
    d, hd = m["hidden_size"], head_dim(m)
    h, k = m["num_attention_heads"], m["num_key_value_heads"]
    return (d * h * hd + 2 * d * k * hd + h * hd * d + d * k + 2 * hd
            + 3 * d * m["intermediate_size"] + 2 * d)


def head_params(m: Dict[str, Any]) -> int:
    return m["hidden_size"] * m["vocab_size"]


def total_params(m: Dict[str, Any]) -> int:
    return (m["num_hidden_layers"] * layer_params(m) + 2 * head_params(m)
            + m["hidden_size"])


def _layer_matmul_params(m: Dict[str, Any]) -> int:
    return layer_params(m) - 2 * m["hidden_size"] - 2 * head_dim(m)


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    return 2.0 * (m["num_hidden_layers"] * _layer_matmul_params(m)
                  + head_params(m))


def pairs_flops(m: Dict[str, Any], n: int) -> float:
    """The attention form of ONE layer over a prompt of n: q . k and a v,
    two multiply-adds of d a (query head, pair), over the n (n + 1) / 2 pairs
    the mask keeps; squaring and decaying a score are not matrix work."""
    return 4.0 * m["num_attention_heads"] * head_dim(m) * n * (n + 1) / 2.0


def state_build_flops(m: Dict[str, Any], n: int) -> float:
    """ONE build of S and z of one layer from n positions: a multiply-add a
    position an element of the state and of its normaliser."""
    return 2.0 * m["num_key_value_heads"] * n * state_rows(m) \
        * (head_dim(m) + 1)


def slot_state_bytes(m: Dict[str, Any]) -> int:
    """A slot's state of ONE layer: S and z of every kv head."""
    return m["num_key_value_heads"] * state_rows(m) * (head_dim(m) + 1) \
        * STATE_BYTES


def retention_prompt_ops_bytes(m: Dict[str, Any], n: int, act_bytes: int
                               ) -> Tuple[float, float]:
    """The prompt operator of ONE layer at its least: the attention form's
    pairs and one build of the state; q, k, v and the gates read, y and the
    state written, once each."""
    d = head_dim(m)
    h, k = m["num_attention_heads"], m["num_key_value_heads"]
    byts = n * ((h + 2 * k) * d * act_bytes + k * 4 + h * d * 4) \
        + slot_state_bytes(m)
    return pairs_flops(m, n) + state_build_flops(m, n), float(byts)


def retention_step_ops_bytes(m: Dict[str, Any], slots: float
                             ) -> Tuple[float, float]:
    """The decode step's operator of ONE layer over `slots` live slots: an
    element of the state is decayed, added to (a multiply-add) and read
    against the group's query heads (a multiply-add each); it crosses HBM
    once in and once out."""
    group = m["num_attention_heads"] // m["num_key_value_heads"]
    elems = m["num_key_value_heads"] * state_rows(m) * (head_dim(m) + 1)
    return slots * elems * (3.0 + 2.0 * group), \
        2.0 * slots * slot_state_bytes(m)


def decode_state_bytes(m: Dict[str, Any], slot_steps: float) -> float:
    """Bytes of recurrent state `slot_steps` (live slots x steps) move: every
    layer's state of a live slot in and out, a step."""
    return 2.0 * m["num_hidden_layers"] * slot_state_bytes(m) * slot_steps


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("arch 'brumby' is served, not trained")


def prefill_flops(m: Dict[str, Any], prompt_len: int) -> float:
    """One prompt's prefill at its least: every position through the blocks'
    matrices, the attention form's pairs and one build of the state a layer,
    the head at the last position only."""
    blocks = 2.0 * m["num_hidden_layers"] * _layer_matmul_params(m) \
        * prompt_len
    mixer = m["num_hidden_layers"] * (pairs_flops(m, prompt_len)
                                      + state_build_flops(m, prompt_len))
    return blocks + mixer + 2.0 * head_params(m)


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int = 0) -> Tuple[float, float]:
    """One decode step over the live slots (their context lengths change
    nothing: that is the mechanism): (operations, bytes). Bytes are what must
    cross HBM once: every weight but the embedding table (a lookup), and each
    live slot's state of every layer in and out."""
    n = len(context_lens)
    step_ops, step_bytes = retention_step_ops_bytes(m, n)
    ops = n * matmul_flops_per_token(m) + m["num_hidden_layers"] * step_ops
    byts = float(weight_bytes) * (total_params(m) - head_params(m)) \
        + m["num_hidden_layers"] * step_bytes
    return ops, byts
