"""Architecture adapter `jamba`: the published `config.json` keys of
`model_type: jamba` (AI21-Jamba2-3B) -> the program's `LlamaConfig`
(ray_tpu/models/llama.py) with what this model adds to that block: a HYBRID
stack, a Mamba-1 mixer (`mamba_*` keys; Jamba's own RMS norms of the time
step, B and C) in every layer but those where `i % attn_layer_period ==
attn_layer_offset`, which are attention with NO positional encoding; a dense
SwiGLU feed-forward in every layer (`num_experts` 1: `expert_layer_*` select
nothing); and a head tied to the embedding. The contract is
benchmark/models/llama.py's. Serve only: the program's training forward
refuses state-space layers by name, so `loss_fn` does.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_jamba as counts  # noqa: F401

# What the block needs of the program's model description beyond llama's.
NEEDS = ("ssm_state", "ssm_expand", "ssm_conv", "ssm_dt_rank", "attn_layers",
         "rope", "tie_embeddings")

CHECK_LEAVES = {"final_norm": ("final_norm",),
                "attn_norm": ("layers", "attn_norm"),
                "norm": ("mamba", "norm"),
                "dt_norm": ("mamba", "dt_norm")}

# One attention layer of four (layer 1), MQA 4 query heads on 1 kv head.
REHEARSE = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 1, "intermediate_size": 128,
            "vocab_size": 256, "num_hidden_layers": 4,
            "attn_layer_period": 4, "attn_layer_offset": 1,
            "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2,
            "mamba_d_conv": 4}


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what this block does not compute, instead of running another
    model under Jamba's name."""
    problems = []
    if model.get("sliding_window") is not None:
        problems.append("a sliding window")
    if not model.get("tie_word_embeddings", False):
        problems.append("an untied head")
    if model.get("hidden_act", "silu") != "silu":
        problems.append(f"hidden_act {model.get('hidden_act')!r}")
    if model.get("num_experts", 1) > 1 or model.get("num_experts_per_tok",
                                                    1) > 1:
        problems.append("sparse experts (num_experts > 1)")
    if model.get("mamba_proj_bias", False):
        problems.append("mamba_proj_bias")
    if not model.get("mamba_conv_bias", True):
        problems.append("a convolution without bias")
    if not counts.attention_layer_indices(model):
        problems.append("no attention layer")
    if problems:
        raise ValueError("arch 'jamba' cannot run this model: "
                         + "; ".join(problems))


def build_config(model: Dict[str, Any], dtypes: Dict[str, str], max_seq: int):
    """Fails here, in the parent before any cluster starts, on a program
    whose model description cannot say what Jamba needs."""
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    check_supported(model)
    missing = [f for f in NEEDS
               if f not in {x.name for x in dataclasses.fields(LlamaConfig)}]
    if missing:
        raise ValueError(
            f"arch 'jamba' needs LlamaConfig fields {missing}, which this "
            "program's ray_tpu/models/llama.py does not have: it cannot run "
            "Jamba's stack (state-space layers among the attention layers, "
            "attention without positions, a tied head)")
    return LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], norm_eps=model["rms_norm_eps"],
        max_seq=int(max_seq), param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]),
        ssm_state=model["mamba_d_state"], ssm_expand=model["mamba_expand"],
        ssm_conv=model["mamba_d_conv"], ssm_dt_rank=model["mamba_dt_rank"],
        attn_layers=counts.attention_layer_indices(model), rope=False,
        tie_embeddings=True)


def init_params(cfg, seed: int):
    from benchmark.models import llama as dense
    return dense.init_params(cfg, seed)


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)


def reference():
    from benchmark import reference_jamba
    return reference_jamba
