"""Architecture adapter `olmoe`: published `config.json` keys of
`model_type: olmoe` -> the program's `LlamaConfig` (ray_tpu/models/llama.py)
with the two properties OLMoE adds to that block: a sparse SwiGLU
feed-forward whose routing weights are the softmax over ALL experts at the
chosen k, not renormalised (`norm_topk_prob: false`), and an RMS norm of q
and k over the whole projection before the split into heads. The contract is
benchmark/models/llama.py's; `intermediate_size` is ONE expert's width.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_olmoe as counts  # noqa: F401
from benchmark.models import llama as dense

# What the block needs of the program's model description beyond llama's.
NEEDS = ("n_experts", "top_k_experts", "norm_topk_prob", "qk_norm",
         "moe_aux_weight")

CHECK_LEAVES = dict(dense.CHECK_LEAVES, router=("layers", "router"),
                    q_norm=("layers", "q_norm"), k_norm=("layers", "k_norm"))

REHEARSE = dict(dense.REHEARSE, num_key_value_heads=4, intermediate_size=32,
                num_experts=8, num_experts_per_tok=2)


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what this block does not compute, instead of running another
    model under OLMoE's name."""
    dense.check_supported(model)
    problems = []
    if model.get("clip_qkv") is not None:
        problems.append("clip_qkv is set")
    if model.get("attention_bias"):
        problems.append("attention_bias")
    if model.get("rope_scaling") is not None:
        problems.append("rope_scaling is set")
    for key in ("shared_expert_intermediate_size", "n_shared_experts",
                "num_shared_experts"):
        if model.get(key):
            problems.append(f"a shared expert ({key})")
    if not (0 < model["num_experts_per_tok"] <= model["num_experts"]):
        problems.append("num_experts_per_tok outside 1..num_experts")
    if problems:
        raise ValueError("arch 'olmoe' cannot run this model: "
                         + "; ".join(problems))


def build_config(model: Dict[str, Any], dtypes: Dict[str, str], max_seq: int):
    """Fails here, in the parent before any cluster starts, on a program
    whose model description cannot say what OLMoE needs."""
    import dataclasses

    from ray_tpu.models.llama import LlamaConfig
    check_supported(model)
    missing = [f for f in NEEDS
               if f not in {x.name for x in dataclasses.fields(LlamaConfig)}]
    if missing:
        raise ValueError(
            f"arch 'olmoe' needs LlamaConfig fields {missing}, which this "
            "program's ray_tpu/models/llama.py does not have: it cannot run "
            "OLMoE's block (routing without renormalisation, q/k norm)")
    # moe_aux_weight 0: the reference's loss is cross-entropy alone.
    return LlamaConfig(
        n_experts=model["num_experts"],
        top_k_experts=model["num_experts_per_tok"],
        norm_topk_prob=bool(model.get("norm_topk_prob", False)),
        qk_norm=True, moe_aux_weight=0.0,
        **dense.to_model_kwargs(model, dtypes, max_seq))


init_params = dense.init_params
loss_fn = dense.loss_fn


def reference():
    from benchmark import reference_olmoe
    return reference_olmoe
