"""Mixture-of-experts routing and experts, for training and for serving.

The reference framework only passes expert-parallel sizes through to vLLM
(SURVEY.md §2.3 — EP row: "Not in Ray"); here MoE is a native layer.

One path, and it computes the published mixture exactly: router logits and
softmax in float32, top-k, the weights either left as the softmax over ALL
experts gives them (OLMoE, `norm_topk_prob: false`) or renormalised over the
selected k (Mixtral), and EVERY assignment computed. There is no capacity and
so no dropped token: a token's output depends on that token alone, never on
who shares its batch. Shapes stay static without a capacity because the
`tokens * k` assignments are sorted by expert and the experts run as grouped
matmuls over the sorted rows (`jax.lax.ragged_dot`: group e is the
`group_sizes[e]` rows after those of the experts before it), so compute is
O(tokens * k * d * f) whatever the skew. The experts' weight leading axis
carries the logical "expert" axis which the sharding rules map onto `ep`.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def top_k_routing(gate_logits: jax.Array, k: int, norm_topk_prob: bool = True
                  ) -> Tuple[jax.Array, jax.Array]:
    """gate_logits: [tokens, n_experts] -> (weights [tokens, k], idx [tokens, k]).

    The weights are the float32 softmax over all experts at the k largest;
    `norm_topk_prob` renormalises them to sum to one (which equals the
    softmax over the selected k, Mixtral's; OLMoE publishes False).
    """
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    weights, idx = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, idx


def moe_ffn(x: jax.Array, gate_w: jax.Array, w_up: jax.Array, w_gate: jax.Array,
            w_down: jax.Array, *, top_k: int = 2, norm_topk_prob: bool = True,
            live: Optional[jax.Array] = None,
            layer: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """SwiGLU MoE feed-forward over sorted assignments.

    x: [tokens, d_model]
    gate_w: [d_model, n_experts] router weights
    w_up/w_gate: [n_experts, d_model, d_ff]; w_down: [n_experts, d_ff, d_model]
    live: [tokens] bool, the rows that are somebody's token (not a bucket's
    padding, not an idle slot). Every row is computed either way, each from
    itself alone; `live` only keeps the others out of the count.
    layer: with it, w_up/w_gate/w_down are the STACKS of all the model's
    layers, `[n_layers, n_experts, ...]`, and `layer` (a traced index) says
    whose experts these tokens meet. The grouped matmul then reads the stack
    where it lies, the other layers' experts as empty groups; handed one
    layer sliced out of a scanned stack, its kernel is first given a copy of
    that layer's experts (0.8 GB a layer at OLMoE's widths, every step).
    Returns (out [tokens, d_model], aux_loss scalar, tokens per expert
    [n_experts] int32 over the live rows).
    """
    tokens, _ = x.shape
    n_experts = gate_w.shape[-1]
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", x, gate_w,
                            preferred_element_type=jnp.float32)
        weights, idx = top_k_routing(logits, top_k, norm_topk_prob)

    with jax.named_scope("moe_dispatch"):
        # Assignments token-major (t, j) -> sorted by expert; the sort is
        # stable, so an expert's rows keep the order of their tokens.
        flat_expert = idx.reshape(-1)                       # [t*k]
        order = jnp.argsort(flat_expert)
        token_of = order // top_k
        chosen = flat_expert[:, None] == jnp.arange(n_experts)[None, :]
        group_sizes = jnp.sum(chosen, axis=0, dtype=jnp.int32)   # [e]
        xs = x[token_of]                                    # [t*k, d]
        groups = group_sizes
        if layer is not None:
            stacked = w_up.shape[0] * n_experts
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros(stacked, jnp.int32), group_sizes,
                (layer * n_experts,))
            w_up, w_gate, w_down = (w.reshape(stacked, *w.shape[2:])
                                    for w in (w_up, w_gate, w_down))

    with jax.named_scope("experts"):
        h = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, groups)) \
            * jax.lax.ragged_dot(xs, w_up, groups)
        ys = jax.lax.ragged_dot(h, w_down, groups)          # [t*k, d]

    with jax.named_scope("moe_combine"):
        # Back to token-major by a gather and one sum over a token's k
        # rows in a fixed order: no scatter-add, whose order of additions
        # (and so the last bit of a token's output) would be the batch's.
        back = jnp.argsort(order)
        per_token = ys[back].reshape(tokens, top_k, -1).astype(jnp.float32)
        out = jnp.einsum("tkd,tk->td", per_token, weights)

    if live is None:
        counts = group_sizes
    else:
        counts = jnp.sum(chosen & jnp.repeat(live, top_k)[:, None], axis=0,
                         dtype=jnp.int32)
    # Load-balancing aux loss (Switch-style): mean prob * mean assignment frac.
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = group_sizes.astype(jnp.float32) / tokens
    aux = n_experts * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return out.astype(x.dtype), aux, counts
