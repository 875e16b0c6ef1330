"""Architecture adapter `granitemoehybrid`: the published `config.json` keys
of Granite-4.0-H-Small (`model_type: granitemoehybrid`) -> the program's
`LlamaConfig` (ray_tpu/models/llama.py) with what this model adds to the
block: a HYBRID stack, a Mamba-2 mixer (`mamba_n_heads` heads of
`mamba_d_head` channels with one scalar decay each, B and C of
`mamba_d_state` numbers in ONE group, a convolution of `mamba_d_conv` taps
over x, B and C together, a gated RMS norm) wherever `layer_types` says
"mamba" and grouped-query attention with NO position signal in the others;
in EVERY layer a softmax router over the chosen, `num_experts_per_tok` of its
outputs, beside a shared expert of `shared_intermediate_size`; a SHARE of the
routed experts (`num_local_experts` counts the experts HELD here,
`expert_parallel` says which of how many: `routed_experts_total`, the
router's width); the family's four scalar multipliers; a head tied to the
embedding. The contract is benchmark/models/llama.py's. Serve only: the
program's training forward refuses state-space layers by name, so `loss_fn`
does.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_granite as counts  # noqa: F401

# What the block needs of the program's model description beyond llama's.
NEEDS = ("ssm_state", "ssm_heads", "ssm_expand", "ssm_conv", "attn_layers",
         "rope", "tie_embeddings", "n_shared_experts", "experts_held",
         "embed_scale", "residual_scale", "logit_scale", "attn_scale")

# Serving only: the program's training forward refuses state-space layers.
CHECK_LEAVES: Dict[str, Any] = {}

# Widths of the rehearsal: 4 Mamba-2 heads of 32 channels on 16 states, one
# attention layer of four (layer 1) with 4 query heads on 2 kv heads of 32,
# 8 experts of 64, 3 a token, experts 0..3 held, a shared expert of 128.
REHEARSE = {
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 64, "shared_intermediate_size": 128,
    "vocab_size": 256, "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 32, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_d_conv": 4, "mamba_chunk_size": 32,
    "num_local_experts": 4, "num_experts_per_tok": 3,
    "attention_multiplier": 0.03125,
    "expert_parallel": {"chips": 2, "rank": 0, "routed_experts_total": 8}}


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what this block does not compute, instead of running another
    model under this one's name."""
    problems = []
    if model.get("hidden_act", "silu") != "silu":
        problems.append(f"hidden_act {model.get('hidden_act')!r}")
    if model.get("mamba_n_groups", 1) != 1:
        problems.append("mamba_n_groups other than 1: B and C are shared by "
                        "every head")
    if model.get("mamba_proj_bias") or model.get("attention_bias"):
        problems.append("a projection bias (mamba_proj_bias, attention_bias)")
    if not model.get("mamba_conv_bias", True):
        problems.append("a convolution without bias")
    if model.get("position_embedding_type", "nope") != "nope":
        problems.append("position_embedding_type "
                        f"{model.get('position_embedding_type')!r}: "
                        "attention takes no position signal here")
    if model.get("tie_word_embeddings") is False:
        problems.append("an untied head")
    if model.get("normalization_function", "rmsnorm") != "rmsnorm":
        problems.append("a norm other than rmsnorm")
    if model.get("mamba_n_heads", 0) * model.get("mamba_d_head", 0) \
            != model.get("mamba_expand", 0) * model["hidden_size"]:
        problems.append("mamba_n_heads x mamba_d_head differs from "
                        "mamba_expand x hidden_size")
    layers = model["num_hidden_layers"]
    types = list(model.get("layer_types") or ())
    if len(types) != layers or set(types) - {"mamba", "attention"}:
        problems.append("layer_types: one of 'mamba' or 'attention' for each "
                        "of num_hidden_layers layers")
    elif "mamba" not in types or "attention" not in types:
        problems.append("layer_types: both kinds of layer")
    width = model.get("intermediate_size", 0)
    if not width or model.get("shared_intermediate_size", 0) % width \
            or not model.get("shared_intermediate_size"):
        problems.append("shared_intermediate_size: a shared expert, a whole "
                        "number of routed experts wide")
    ep = model.get("expert_parallel")
    held = model.get("num_local_experts", 0)
    total = ep.get("routed_experts_total", 0) if ep else held
    if ep and (not total or total % held or ep.get("chips") != total // held
               or not 0 <= ep.get("rank", -1) < total // held):
        problems.append("expert_parallel does not say which num_local_experts "
                        "of routed_experts_total are held (chips, rank)")
    if held < 1 or not 0 < model.get("num_experts_per_tok", 0) <= total:
        problems.append("num_local_experts and num_experts_per_tok: a sparse "
                        "feed-forward in every layer")
    if problems:
        raise ValueError("arch 'granitemoehybrid' cannot run this model: "
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
            f"arch 'granitemoehybrid' needs LlamaConfig fields {missing}, "
            "which this program's ray_tpu/models/llama.py does not have: it "
            "cannot run this stack (Mamba-2 layers among attention layers "
            "over a share of sparse experts beside a shared expert, the "
            "family's scalar multipliers)")
    held = model["num_local_experts"]
    ep = model.get("expert_parallel")
    # d_ff: one routed expert's width; moe_aux_weight 0: serving takes no
    # loss.
    return LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], norm_eps=model["rms_norm_eps"],
        max_seq=int(max_seq), param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]),
        ssm_state=model["mamba_d_state"], ssm_heads=model["mamba_n_heads"],
        ssm_expand=model["mamba_expand"], ssm_conv=model["mamba_d_conv"],
        attn_layers=tuple(i for i, kind in enumerate(model["layer_types"])
                          if kind == "attention"),
        rope=False, tie_embeddings=True,
        n_experts=ep["routed_experts_total"] if ep else held,
        top_k_experts=model["num_experts_per_tok"], norm_topk_prob=True,
        moe_aux_weight=0.0,
        experts_held=(ep["rank"] * held, held) if ep else None,
        n_shared_experts=model["shared_intermediate_size"]
        // model["intermediate_size"],
        embed_scale=float(model.get("embedding_multiplier", 1.0)),
        residual_scale=float(model.get("residual_multiplier", 1.0)),
        logit_scale=1.0 / float(model.get("logits_scaling", 1.0)),
        attn_scale=float(model["attention_multiplier"]))


def init_params(cfg, seed: int):
    from benchmark.models import llama as dense
    return dense.init_params(cfg, seed)


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)   # refuses, by name


def reference():
    from benchmark import reference_granite
    return reference_granite
