"""Architecture adapter `sdar`: the published `config.json` keys of
SDAR-30B-A3B-Chat (`model_type: sdar_moe`: every key a `Qwen3MoeConfig` key)
and the three sizes of its generation that the configuration file `assumed`
(`block_length`, `denoise_steps`, `mask_id`) -> the program's `LlamaConfig`
(ray_tpu/models/llama.py): the Qwen3-MoE block (a head width that is a key of
its own, an RMS norm of q and k over each head, plain RoPE, a sparse SwiGLU
feed-forward renormalised over the chosen experts whose width is
`moe_intermediate_size`) under the mask of generation by blocks, a position
predicting its own token. The contract is benchmark/models/llama.py's.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_sdar as counts  # noqa: F401
from benchmark.models import llama as dense

# What the block needs of the program's model description beyond llama's.
NEEDS = ("n_experts", "top_k_experts", "norm_topk_prob", "qk_norm",
         "moe_aux_weight", "head_dim", "block_length", "denoise_steps",
         "mask_id")

CHECK_LEAVES = dict(dense.CHECK_LEAVES, router=("layers", "router"),
                    q_norm=("layers", "q_norm"), k_norm=("layers", "k_norm"))

# head_dim is not hidden_size / heads; the mask id is a row of the tiny
# vocabulary.
REHEARSE = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "intermediate_size": 128, "moe_intermediate_size": 32,
    "vocab_size": 256, "num_hidden_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "mask_id": 255}


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what this block does not compute, instead of running another
    model under SDAR's name."""
    problems = []
    if model.get("sliding_window") is not None or model.get(
            "use_sliding_window"):
        problems.append("a sliding window")
    if model.get("tie_word_embeddings"):
        problems.append("tied embeddings")
    if model.get("hidden_act", "silu") != "silu":
        problems.append(f"hidden_act {model.get('hidden_act')!r}")
    if model.get("attention_bias"):
        problems.append("attention_bias")
    for key in ("shared_expert_intermediate_size", "n_shared_experts",
                "num_shared_experts"):
        if model.get(key):
            problems.append(f"a shared expert ({key})")
    if model.get("decoder_sparse_step", 1) != 1 or model.get("mlp_only_layers"):
        problems.append("dense layers among the sparse ones")
    if not (0 < model["num_experts_per_tok"] <= model["num_experts"]):
        problems.append("num_experts_per_tok outside 1..num_experts")
    if model.get("rope_scaling"):
        problems.append("rope_scaling")
    for key in ("block_length", "denoise_steps", "mask_id"):
        if not isinstance(model.get(key), int):
            problems.append(f"no {key} (the configuration file assumes it)")
    if problems:
        raise ValueError("arch 'sdar' cannot run this model: "
                         + "; ".join(problems))


def build_config(model: Dict[str, Any], dtypes: Dict[str, str], max_seq: int):
    """Fails here, in the parent before any cluster starts, on a program
    whose model description cannot say what SDAR needs."""
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    check_supported(model)
    missing = [f for f in NEEDS
               if f not in {x.name for x in dataclasses.fields(LlamaConfig)}]
    if missing:
        raise ValueError(
            f"arch 'sdar' needs LlamaConfig fields {missing}, which this "
            "program's ray_tpu/models/llama.py does not have: it cannot "
            "generate by blocks (the block mask, a step of block_length rows "
            "a slot, the commit by confidence)")
    kw = {field: model[key] for key, field in dense.KEYS.items()}
    # One expert's width; `intermediate_size` is the dense width no layer has.
    kw["d_ff"] = model["moe_intermediate_size"]
    # moe_aux_weight 0: serving only.
    return LlamaConfig(
        max_seq=int(max_seq), param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]), head_dim=model["head_dim"],
        qk_norm="head", n_experts=model["num_experts"],
        top_k_experts=model["num_experts_per_tok"],
        norm_topk_prob=bool(model.get("norm_topk_prob", False)),
        moe_aux_weight=0.0, block_length=model["block_length"],
        denoise_steps=model["denoise_steps"], mask_id=model["mask_id"], **kw)


init_params = dense.init_params


def loss_fn(params, tokens, cfg, pctx):
    raise NotImplementedError(
        "arch 'sdar' is served, not trained: its loss is the masked-block "
        "objective of arXiv:2510.06303, which the program does not build")


def reference():
    from benchmark import reference_sdar
    return reference_sdar
