"""Architecture adapter `laguna`: the published `config.json` keys of
Laguna-S-2.1 (`model_type: laguna`) -> the program's `LlamaConfig`
(ray_tpu/models/llama.py) with what this model adds to the block: two kinds of
ATTENTION in one stack (`layer_types`: `full_attention`,
`sliding_attention` over `sliding_window` positions) that differ in their
QUERY HEADS (`num_attention_heads_per_layer`: 48 and 72 on
`num_key_value_heads` 8) and in their rotation (`rope_parameters`, an entry a
kind: the window layers turn the whole head at theta 1e4, the full layers
half of it under YaRN with `attention_factor` on cos and sin); a sigmoid gate
a query head on attention's output (`gating` `per-head`); a leading dense
layer (`mlp_layer_types`); a softmax router renormalised over its
`num_experts_per_tok` and scaled by `moe_routed_scaling_factor`, a shared
expert of `shared_expert_intermediate_size` beside the routed ones; and a
SHARE of the routed experts: `num_experts` counts the experts HELD here and
`expert_parallel` says which of how many (`routed_experts_total`, the
router's width). The lists that have an entry a layer are read at their first
`num_hidden_layers` entries. The contract is benchmark/models/llama.py's.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_laguna as counts  # noqa: F401

# What the block needs of the program's model description beyond llama's.
NEEDS = ("attn_pattern", "window", "window_kv_heads", "window_rope_theta",
         "window_heads", "rotary_dim", "window_rotary_dim", "rope_yarn",
         "rope_magnitude", "attn_gate", "first_dense", "d_ff_dense",
         "routed_scale", "n_shared_experts", "experts_held")

# Serving only: the program's training forward refuses mixed attention.
CHECK_LEAVES: Dict[str, Any] = {}

FULL, WINDOW = "full_attention", "sliding_attention"

# Widths of the rehearsal: heads of 16 on 2 kv heads, 4 query heads (2 a kv
# head) in the full layers, which turn 8 numbers under YaRN, and 6 (3 a kv
# head) in the window layers, which turn all 16; a window of 16; a dense layer
# then window, window, full, window, window (the window layers two runs of
# one stack); 16 experts, 4 a token, experts 4..7 held here, a shared expert.
REHEARSE = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "sliding_window": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "vocab_size": 256, "num_hidden_layers": 6,
    "layer_types": [FULL, WINDOW, WINDOW, FULL, WINDOW, WINDOW],
    "num_attention_heads_per_layer": [4, 6, 6, 4, 6, 6],
    "mlp_layer_types": ["dense"] + ["sparse"] * 5,
    "gating_types": ["per_head"] * 6, "mlp_only_layers": [0],
    "num_experts": 4, "num_experts_per_tok": 4,
    "expert_parallel": {"chips": 4, "rank": 1, "routed_experts_total": 16}}


# A list with an entry a layer, at the layers held.
per_layer = counts.per_layer


def kind_heads(model: Dict[str, Any]) -> Dict[str, int]:
    """kind of attention -> its query heads, where each kind has one count."""
    out: Dict[str, set] = {}
    for kind, n in zip(per_layer(model, "layer_types"),
                       per_layer(model, "num_attention_heads_per_layer")):
        out.setdefault(kind, set()).add(n)
    return {k: v.pop() if len(v) == 1 else 0 for k, v in out.items()}


def rotary_dim(model: Dict[str, Any], kind: str) -> int:
    return int(model["head_dim"]
               * model["rope_parameters"][kind]["partial_rotary_factor"])


def first_dense(model: Dict[str, Any]) -> int:
    """The leading layers whose `mlp_layer_types` is `dense`."""
    types = per_layer(model, "mlp_layer_types")
    return types.index("sparse") if "sparse" in types else len(types)


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what this block does not compute, instead of running another
    model under this one's name."""
    problems = []
    if model.get("tie_word_embeddings"):
        problems.append("tied embeddings")
    if model.get("hidden_act", "silu") != "silu":
        problems.append(f"hidden_act {model.get('hidden_act')!r}")
    if model.get("attention_bias"):
        problems.append("attention_bias")
    if model.get("gating") != "per-head" \
            or set(per_layer(model, "gating_types")) != {"per_head"}:
        problems.append("a gate other than one a query head in every layer "
                        "(gating 'per-head', gating_types 'per_head')")
    if model.get("moe_router_logit_softcapping"):
        problems.append("a cap on the router's logits "
                        "(moe_router_logit_softcapping)")
    if model.get("moe_apply_router_weight_on_input"):
        problems.append("the router's weight on an expert's input "
                        "(moe_apply_router_weight_on_input)")
    layers = model["num_hidden_layers"]
    kinds, mlps, per = (per_layer(model, k) for k in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"))
    if len(kinds) != layers or len(mlps) != layers or len(per) != layers \
            or set(kinds) - {FULL, WINDOW} or set(mlps) - {"dense", "sparse"}:
        problems.append("layer_types, mlp_layer_types and "
                        "num_attention_heads_per_layer: an entry for each of "
                        "num_hidden_layers layers, full_attention or "
                        "sliding_attention, dense or sparse")
    else:
        dense = first_dense(model)
        if not 0 < dense < layers or "dense" in mlps[dense:] \
                or list(model.get("mlp_only_layers", range(dense))) \
                != list(range(dense)) \
                or model.get("decoder_sparse_step", 1) != 1:
            problems.append("mlp_layer_types: leading dense layers, then "
                            "sparse ones only")
        elif WINDOW in kinds[:dense]:
            problems.append("a leading dense layer with window attention")
        heads = kind_heads(model)
        if not all(heads.values()) or any(
                n % model["num_key_value_heads"] for n in heads.values()):
            problems.append("num_attention_heads_per_layer: one count a kind "
                            "of attention, whole groups a kv head")
        elif heads.get(FULL, model["num_attention_heads"]) \
                != model["num_attention_heads"]:
            problems.append("num_attention_heads is not the full layers'")
    rope = model.get("rope_parameters") or {}
    for kind in (FULL, WINDOW):
        rp = rope.get(kind) or {}
        r = int(model["head_dim"] * rp.get("partial_rotary_factor", 0))
        if r % 2 or not 0 < r <= model["head_dim"]:
            problems.append(f"rope_parameters[{kind}].partial_rotary_factor: "
                            "an even part of head_dim")
        want = ("default", "yarn") if kind == FULL else ("default",)
        if rp.get("rope_type") not in want:
            problems.append(f"rope_parameters[{kind}].rope_type "
                            f"{rp.get('rope_type')!r}: {' or '.join(want)}")
    shared, expert = (model.get(k) or 0 for k in (
        "shared_expert_intermediate_size", "moe_intermediate_size"))
    if not expert or not shared or shared % expert:
        problems.append("shared_expert_intermediate_size: whole multiples of "
                        "moe_intermediate_size (the shared expert has the "
                        "routed experts' form)")
    ep = model.get("expert_parallel") or {}
    total = ep.get("routed_experts_total", 0)
    held = model["num_experts"]
    if not total or total % held or ep.get("chips") != total // held \
            or not 0 <= ep.get("rank", -1) < total // held:
        problems.append("expert_parallel does not say which num_experts "
                        "of how many are held (chips, rank, "
                        "routed_experts_total)")
    elif model["num_experts_per_tok"] > total:
        problems.append("num_experts_per_tok exceeds the experts")
    if problems:
        raise ValueError("arch 'laguna' cannot run this model: "
                         + "; ".join(problems))


def build_config(model: Dict[str, Any], dtypes: Dict[str, str], max_seq: int):
    """Fails here, in the parent before any cluster starts, on a program
    whose model description cannot say what this model needs."""
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    check_supported(model)
    missing = [f for f in NEEDS
               if f not in {x.name for x in dataclasses.fields(LlamaConfig)}]
    if missing:
        raise ValueError(
            f"arch 'laguna' needs LlamaConfig fields {missing}, which this "
            "program's ray_tpu/models/llama.py does not have: it cannot run "
            "this block (query heads and a rotation by kind of attention, "
            "YaRN with a magnitude in a mixed stack, a gate a head, a shared "
            "expert and a scaled softmax router beside window attention)")
    ep, held = model["expert_parallel"], model["num_experts"]
    heads = kind_heads(model)
    full, window = (model["rope_parameters"][k] for k in (FULL, WINDOW))
    yarn = full["rope_type"] == "yarn"
    # d_ff: one expert's width; moe_aux_weight 0: serving takes no loss.
    return LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["moe_intermediate_size"], max_seq=int(max_seq),
        rope_theta=float(full["rope_theta"]), norm_eps=model["rms_norm_eps"],
        param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]),
        attn_pattern=tuple(int(k == WINDOW)
                           for k in per_layer(model, "layer_types")),
        window=model["sliding_window"],
        window_kv_heads=model["num_key_value_heads"],
        window_heads=heads.get(WINDOW, 0),
        window_rope_theta=float(window["rope_theta"]),
        rotary_dim=rotary_dim(model, FULL),
        window_rotary_dim=rotary_dim(model, WINDOW),
        rope_yarn=tuple(float(full[k]) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow")) if yarn else None,
        rope_magnitude=float(full["attention_factor"]) if yarn else 1.0,
        attn_gate=True,
        first_dense=first_dense(model), d_ff_dense=model["intermediate_size"],
        n_experts=ep["routed_experts_total"],
        top_k_experts=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]), moe_aux_weight=0.0,
        router_score="softmax",
        routed_scale=float(model.get("moe_routed_scaling_factor") or 1.0),
        n_shared_experts=model["shared_expert_intermediate_size"]
        // model["moe_intermediate_size"],
        experts_held=(ep["rank"] * held, held))


def init_params(cfg, seed: int):
    """Weights on the device from the seed, as every adapter's."""
    from benchmark.models import llama as dense
    return dense.init_params(cfg, seed)


def _model_of(cfg):
    """The published keys `reference_laguna` reads, back from the program's
    config (`build_config` undone)."""
    offset, held = cfg.experts_held
    full = {"rope_type": "default", "rope_theta": cfg.rope_theta,
            "partial_rotary_factor": cfg.rotary_dim / cfg.head_dim}
    if cfg.rope_yarn:
        factor, original, fast, slow = cfg.rope_yarn[:4]
        full.update(rope_type="yarn", factor=factor,
                    original_max_position_embeddings=original,
                    beta_fast=fast, beta_slow=slow,
                    attention_factor=cfg.rope_magnitude)
    window = cfg.attention_kind("window")
    return {
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.n_layers,
        "sliding_window": cfg.window, "rms_norm_eps": cfg.norm_eps,
        "rope_parameters": {
            FULL: full,
            WINDOW: {"rope_type": "default", "rope_theta": window.theta,
                     "partial_rotary_factor":
                         window.rotary_dim / cfg.head_dim}},
        "layer_types": [WINDOW if k else FULL for k in cfg.attn_pattern],
        "mlp_layer_types": ["sparse" if i >= cfg.first_dense else "dense"
                            for i in range(cfg.n_layers)],
        "num_experts_per_tok": cfg.top_k_experts,
        "norm_topk_prob": cfg.norm_topk_prob,
        "moe_routed_scaling_factor": cfg.routed_scale, "num_experts": held,
        "expert_parallel": {"rank": offset // held,
                            "routed_experts_total": cfg.n_experts}}


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)   # refuses, by name


def reference():
    from benchmark import reference_laguna
    return reference_laguna
