"""Operations and bytes of `arch: sdar`, by the rules at the top of
benchmark/flops.py: what the mathematics requires, a multiply-add is two
operations. The block is Qwen3-MoE's (benchmark/flops_keye.py without the
indexer); what generation by blocks changes is what a "step" is:

* a FORWARD carries `rows` = slots x block_length rows, reads every weight
  outside the routed experts once, the experts TOUCHED, and each live slot's
  K and V once for all the block's rows; a block is `denoise_steps` forwards
  with the head and one, the commit, without it;
* attention under the block mask keeps, for a query at position t, the (floor(t
  / B) + 1) B keys of its own block and every block before it: `block_pairs`;
* the two attention kernels: a prompt's (`block_flash_fwd`) and a forward's
  (`paged_decode` at B x the query heads of a kv head).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops import head_params
from benchmark import flops_olmoe
from benchmark.flops_olmoe import attention_params, router_params  # noqa: F401


def sizes(m: Dict[str, Any]) -> Tuple[int, int]:
    """(block_length, denoise_steps)."""
    return int(m["block_length"]), int(m["denoise_steps"])


def layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(layers, sparse layers): every layer routes."""
    return m["num_hidden_layers"], m["num_hidden_layers"]


def attention_layers(m: Dict[str, Any]) -> int:
    return m["num_hidden_layers"]


def forwards_per_block(m: Dict[str, Any]) -> int:
    """The denoising forwards and the commit."""
    return sizes(m)[1] + 1


def forwards_per_position(m: Dict[str, Any]) -> float:
    """Forwards a position of a block costs its slot: (T + 1) / B."""
    return forwards_per_block(m) / sizes(m)[0]


def expert_params(m: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def norm_params(m: Dict[str, Any]) -> int:
    """attn_norm, mlp_norm and the per-head q and k norms."""
    return 2 * m["hidden_size"] + 2 * m["head_dim"]


def active_layer_weights(m: Dict[str, Any]) -> int:
    """Weights one row multiplies in one block."""
    return (attention_params(m) + router_params(m)
            + m["num_experts_per_tok"] * expert_params(m))


def layer_params(m: Dict[str, Any]) -> int:
    return (attention_params(m) + router_params(m)
            + m["num_experts"] * expert_params(m) + norm_params(m))


def total_params(m: Dict[str, Any]) -> int:
    return (m["num_hidden_layers"] * layer_params(m) + 2 * head_params(m)
            + m["hidden_size"])


def matmul_flops_per_token(m: Dict[str, Any], head: bool = True) -> float:
    """One row through the blocks' matrices, and the head's where it runs."""
    return 2.0 * (m["num_hidden_layers"] * active_layer_weights(m)
                  + (head_params(m) if head else 0))


def block_pairs(m: Dict[str, Any], n: int) -> float:
    """(query, key) pairs the block mask keeps over positions 0..n-1, n whole
    blocks: query t keeps (floor(t / B) + 1) B keys."""
    B = sizes(m)[0]
    blocks = n // B
    return float(B) * B * blocks * (blocks + 1) / 2.0


def attention_flops(m: Dict[str, Any], pairs: float) -> float:
    """QK^T and PV over `pairs` (query, key) pairs, one layer."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"] * pairs


def kept_rows(m: Dict[str, Any], prompt_len: int) -> int:
    """Rows of a prompt its prefill keeps: its whole blocks."""
    B = sizes(m)[0]
    return prompt_len // B * B


def prefill_flops(m: Dict[str, Any], prompt_len: int) -> float:
    """One prompt's prefill: its whole blocks through the layers under the
    block mask; no head (the prefill yields no token)."""
    n = kept_rows(m, prompt_len)
    layers_ = m["num_hidden_layers"]
    return (2.0 * layers_ * active_layer_weights(m) * n
            + layers_ * attention_flops(m, block_pairs(m, n)))


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("arch 'sdar' is served, not trained")


def prefill_attn_ops_bytes(m: Dict[str, Any], prompt_len: int,
                           elem_bytes: int) -> Tuple[float, float]:
    """ONE layer's attention over a prompt's kept rows (the program's
    `block_flash_fwd`): (operations, bytes): the pairs the block mask keeps;
    q and the result of every query head, K and V of every kv head, each
    crossing HBM once."""
    n = kept_rows(m, prompt_len)
    heads = 2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"]
    return (attention_flops(m, block_pairs(m, n)),
            float(n) * heads * m["head_dim"] * elem_bytes)


def decode_attn_ops_bytes(m: Dict[str, Any], live_positions: float,
                          slots: float, elem_bytes: int
                          ) -> Tuple[float, float]:
    """ONE layer's attention of ONE forward (the program's `paged_decode` at
    B rows a slot) over `slots` live slots that hold `live_positions`
    positions in all, their open blocks included: (operations, bytes). Every
    one of a block's B rows attends to every live position of its slot; K
    and V of those positions are read ONCE for the B rows; the B rows' q and
    result cross once."""
    B = sizes(m)[0]
    kv_row = 2 * m["num_key_value_heads"] * m["head_dim"] * elem_bytes
    qo = 2.0 * slots * B * m["num_attention_heads"] * m["head_dim"] \
        * elem_bytes
    return (attention_flops(m, B * live_positions),
            kv_row * live_positions + qo)


def experts_ops_bytes(m: Dict[str, Any], assignments: float, touched: float,
                      weight_bytes: int, act_bytes: int) -> Tuple[float, float]:
    """The grouped matmuls of ONE layer over `assignments` rows that touch
    `touched` distinct experts: flops_olmoe's count at this model's expert
    width."""
    return flops_olmoe.experts_ops_bytes(
        dict(m, intermediate_size=m["moe_intermediate_size"]), assignments,
        touched, weight_bytes, act_bytes)


def forward_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                      kv_bytes: int, *, experts_touched: float,
                      head: bool = True) -> Tuple[float, float]:
    """ONE forward of B rows for each live slot, the slots holding
    `context_lens` positions (their open blocks included): (operations,
    bytes). Bytes are what must cross HBM once: attention's and the router's
    weights, the weights of the `experts_touched` distinct experts a layer,
    the head's where it runs, the mask's row of the embedding, K and V of
    each slot's positions."""
    B = sizes(m)[0]
    layers_ = m["num_hidden_layers"]
    slots = len(context_lens)
    weights = (layers_ * (attention_params(m) + router_params(m)
                          + norm_params(m)
                          + experts_touched * expert_params(m))
               + (head_params(m) if head else 0) + m["hidden_size"])
    ops, byts = decode_attn_ops_bytes(m, float(sum(context_lens)), slots,
                                      kv_bytes)
    return (slots * B * matmul_flops_per_token(m, head) + layers_ * ops,
            float(weight_bytes) * weights + layers_ * byts)


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int, *, experts_touched: float
                          ) -> Tuple[float, float]:
    """What ONE position of a chunk costs, as the readers that divide a
    decode program by `decode_chunk` count a step: a block's T forwards with
    the head and its commit without, over the block's B positions.
    `experts_touched` is then the program's counter over `decode_chunk x
    layers`, the distinct experts a layer a FORWARD times the forwards a
    position."""
    B, T = sizes(m)
    per_forward = experts_touched / forwards_per_position(m)
    with_head = forward_ops_bytes(m, context_lens, weight_bytes, kv_bytes,
                                  experts_touched=per_forward)
    commit = forward_ops_bytes(m, context_lens, weight_bytes, kv_bytes,
                               experts_touched=per_forward, head=False)
    return tuple((T * a + b) / B for a, b in zip(with_head, commit))
