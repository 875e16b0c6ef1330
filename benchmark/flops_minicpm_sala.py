"""Operations and bytes of `arch: minicpm_sala` (MiniCPM-SALA: decayed linear
attention beside attention that selects blocks), by the rules at the top of
`flops.py`: what the mathematics requires, a multiply-add two operations,
from shapes alone. `m` holds the published `config.json` keys; `mixer_types`
is read at its first `num_hidden_layers` entries.

The LINEAR layer is counted as the LEAST work of the recurrence: a position
adds one outer product to a head's state (a multiply-add an element) and
reads it against one query (a multiply-add an element), `4 d^2` a head a
position, whatever chunks the program works in (a chunk's own pairs are more
work for the same numbers); its state `heads x d x d` float32, what a decode
step moves in and out. The SPARSE layer is counted at the keys a query READS:
every earlier key while its context is under `dense_len`, from there on
`topk` blocks (all of them where there are no more; its own block to its own
position), and the scoring against the pooled keys it may see, one every
`kernel_stride` positions. A program that scores every causal pair under a
mask, or reads whole pages, does more than is counted: every share built on
these can only under-read.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

SPARSE, LINEAR = "minicpm4", "lightning-attn"
STATE_BYTES = 4     # the linear state is float32 (the configuration, assumed)

# `sparse_config` as MiniCPM4 publishes it; a configuration states its own.
SPARSE_SIZES = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                "topk": 64, "init_blocks": 1, "window_size": 2048,
                "dense_len": 8192}


def sparse_sizes(m: Dict[str, Any]) -> Dict[str, int]:
    return dict(SPARSE_SIZES, **(m.get("sparse_config") or {}))


def per_layer(m: Dict[str, Any], key: str = "mixer_types") -> List[Any]:
    """A list with an entry a layer, at the layers held."""
    return list(m[key][:m["num_hidden_layers"]])


def layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(dense layers, sparse layers) in the feed-forward's sense, as every
    adapter's: every layer has the dense SwiGLU."""
    return m["num_hidden_layers"], 0


def mixer_layers(m: Dict[str, Any]) -> Tuple[int, int]:
    """(block-sparse attention layers, linear layers) held here."""
    kinds = per_layer(m)
    return kinds.count(SPARSE), kinds.count(LINEAR)


def _ffn_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def _gate(m: Dict[str, Any], key: str, width: int) -> int:
    return m["hidden_size"] * width if m.get(key) else 0


def sparse_layer_matmul_params(m: Dict[str, Any]) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + _gate(m, "attn_use_output_gate", q) \
        + _ffn_params(m)


def linear_layer_matmul_params(m: Dict[str, Any]) -> int:
    d = m["hidden_size"]
    q = m["lightning_nh"] * m["lightning_head_dim"]
    kv = m["lightning_nkv"] * m["lightning_head_dim"]
    return d * q + 2 * d * kv + q * d + _gate(m, "use_output_gate", q) \
        + _ffn_params(m)


def norm_params(m: Dict[str, Any]) -> int:
    """Every norm's weights: two a layer, the final one, and a linear
    layer's q/k norms a head and its output norm."""
    n_sparse, n_linear = mixer_layers(m)
    hd = m["lightning_head_dim"]
    linear = (2 * hd if m.get("qk_norm") else 0) \
        + (m["lightning_nh"] * hd if m.get("use_output_norm") else 0)
    return 2 * m["hidden_size"] * (n_sparse + n_linear) + m["hidden_size"] \
        + n_linear * linear


def head_params(m: Dict[str, Any]) -> int:
    return m["hidden_size"] * m["vocab_size"]


def total_params(m: Dict[str, Any]) -> int:
    n_sparse, n_linear = mixer_layers(m)
    return (n_sparse * sparse_layer_matmul_params(m)
            + n_linear * linear_layer_matmul_params(m) + norm_params(m)
            + 2 * head_params(m))


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    n_sparse, n_linear = mixer_layers(m)
    return 2.0 * (n_sparse * sparse_layer_matmul_params(m)
                  + n_linear * linear_layer_matmul_params(m)
                  + head_params(m))


# ---------------------------------------------------------------------------
# The linear layer
# ---------------------------------------------------------------------------

def linear_token_flops(m: Dict[str, Any]) -> float:
    """ONE position of ONE linear layer by the recurrence: the state's update
    and its read, a multiply-add an element each."""
    d = m["lightning_head_dim"]
    return 4.0 * m["lightning_nh"] * d * d


def linear_state_bytes(m: Dict[str, Any]) -> int:
    """A slot's state of ONE linear layer."""
    d = m["lightning_head_dim"]
    return m["lightning_nh"] * d * d * STATE_BYTES


def linear_prompt_ops_bytes(m: Dict[str, Any], n: int, act_bytes: int
                            ) -> Tuple[float, float]:
    """The prompt operator of ONE linear layer at its least: the recurrence
    over n positions; q, k and v read, the rows and the final state written,
    once each."""
    width = m["lightning_nh"] * m["lightning_head_dim"]
    return n * linear_token_flops(m), \
        float(n * 4 * width * act_bytes + linear_state_bytes(m))


def linear_step_ops_bytes(m: Dict[str, Any], slots: float
                          ) -> Tuple[float, float]:
    """The decode step's operator of ONE linear layer over `slots` live
    slots: the recurrence's step, and the state once in and once out."""
    return slots * linear_token_flops(m), 2.0 * slots * linear_state_bytes(m)


def decode_state_bytes(m: Dict[str, Any], slot_steps: float) -> float:
    """Bytes of recurrent state `slot_steps` (live slots x steps) move: every
    linear layer's state of a live slot in and out, a step."""
    return 2.0 * mixer_layers(m)[1] * linear_state_bytes(m) * slot_steps


# ---------------------------------------------------------------------------
# The sparse layer
# ---------------------------------------------------------------------------

def keys_read(m: Dict[str, Any], t: int) -> int:
    """Keys the query at position t reads."""
    s = sparse_sizes(m)
    if t + 1 < s["dense_len"]:
        return t + 1
    own = t // s["block_size"]
    return (min(own + 1, s["topk"]) - 1) * s["block_size"] \
        + t % s["block_size"] + 1


def pooled_seen(m: Dict[str, Any], t: int) -> int:
    """Pooled keys the query at position t scores (none under dense_len)."""
    s = sparse_sizes(m)
    if t + 1 < s["dense_len"]:
        return 0
    return max((t + 1 - s["kernel_size"]) // s["kernel_stride"] + 1, 0)


def sparse_prompt_pairs(m: Dict[str, Any], n: int) -> Tuple[float, float]:
    """(query-key pairs read, query-pooled-key pairs scored) of ONE sparse
    layer over a prompt of n: a position at a time."""
    return (float(sum(keys_read(m, t) for t in range(n))),
            float(sum(pooled_seen(m, t) for t in range(n))))


def sparse_attention_flops(m: Dict[str, Any], pairs: float) -> float:
    """q . k and p v over `pairs` (query, key) pairs, every query head."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"] * pairs


def sparse_score_flops(m: Dict[str, Any], pairs: float) -> float:
    """q . kbar over `pairs` (query, pooled key) pairs, every query head."""
    return 2.0 * m["num_attention_heads"] * m["head_dim"] * pairs


def block_sparse_prompt_ops_bytes(m: Dict[str, Any], n: int, act_bytes: int
                                  ) -> Tuple[float, float]:
    """Attention under the selection of ONE sparse layer over a prompt of n
    at its least (the selection's scoring is `block_select_ops_bytes`'): q, k
    and v read and the rows written, once each."""
    read, _ = sparse_prompt_pairs(m, n)
    hd = m["head_dim"]
    width = (2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"]) * hd
    return sparse_attention_flops(m, read), float(n * width * act_bytes)


def block_select_ops_bytes(m: Dict[str, Any], n: int, act_bytes: int
                           ) -> Tuple[float, float]:
    """The scoring of ONE sparse layer over a prompt of n: every query head
    against the pooled keys it may see; q and the pooled keys read once."""
    _, scored = sparse_prompt_pairs(m, n)
    hd = m["head_dim"]
    byts = n * m["num_attention_heads"] * hd * act_bytes \
        + n // sparse_sizes(m)["kernel_stride"] \
        * m["num_key_value_heads"] * hd * act_bytes
    return sparse_score_flops(m, scored), float(byts)


def block_sparse_decode_ops_bytes(m: Dict[str, Any], blocks: float,
                                  act_bytes: int) -> Tuple[float, float]:
    """ONE sparse layer's decode attention over `blocks` (kv head, block)
    reads: a block's keys and values of ONE kv head cross HBM once, against
    that kv head's query heads."""
    s = sparse_sizes(m)
    hd = m["head_dim"]
    group = m["num_attention_heads"] // m["num_key_value_heads"]
    keys = blocks * s["block_size"]
    return 4.0 * group * hd * keys, 2.0 * keys * hd * act_bytes


# ---------------------------------------------------------------------------
# The whole programs
# ---------------------------------------------------------------------------

def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError("arch 'minicpm_sala' is served, not trained")


def prefill_flops(m: Dict[str, Any], prompt_len: int) -> float:
    """One prompt's prefill at its least: every position through the blocks'
    matrices, the linear layers' recurrence, the sparse layers' keys read and
    pooled keys scored, the head at the last position only."""
    n_sparse, n_linear = mixer_layers(m)
    blocks = 2.0 * prompt_len * (
        n_sparse * sparse_layer_matmul_params(m)
        + n_linear * linear_layer_matmul_params(m))
    read, scored = sparse_prompt_pairs(m, prompt_len) if n_sparse else (0, 0)
    mixers = n_linear * prompt_len * linear_token_flops(m) + n_sparse * (
        sparse_attention_flops(m, read) + sparse_score_flops(m, scored))
    return blocks + mixers + 2.0 * head_params(m)


def decode_step_ops_bytes(m: Dict[str, Any], context_lens, weight_bytes: int,
                          kv_bytes: int = 0) -> Tuple[float, float]:
    """One decode step over the live slots at these contexts: (operations,
    bytes). Bytes are what must cross HBM once: every weight but the
    embedding table (a lookup), each live slot's linear state in and out, and
    the K and V a sparse layer reads of it."""
    n_sparse, n_linear = mixer_layers(m)
    n = len(context_lens)
    step_ops, step_bytes = linear_step_ops_bytes(m, n)
    keys = sum(keys_read(m, int(c) - 1) for c in context_lens if c >= 1)
    scored = sum(pooled_seen(m, int(c) - 1) for c in context_lens if c >= 1)
    hd, kvh = m["head_dim"], m["num_key_value_heads"]
    ops = n * matmul_flops_per_token(m) + n_linear * step_ops + n_sparse * (
        sparse_attention_flops(m, keys) + sparse_score_flops(m, scored))
    byts = float(weight_bytes) * (total_params(m) - head_params(m)) \
        + n_linear * step_bytes \
        + n_sparse * kv_bytes * (2.0 * keys + scored) * kvh * hd
    return ops, byts
