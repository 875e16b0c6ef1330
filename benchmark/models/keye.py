"""Architecture adapter `keye`: the published `config.json` keys of
Keye-VL-2.0-30B-A3B's language model (`model_type: KeyeVL2`, every key a
`Qwen3MoeConfig` key plus `sa_config`) -> the program's `LlamaConfig`
(ray_tpu/models/llama.py) with what this model adds to that block: a head
width that is a key of its own (128, not hidden_size / heads), an RMS norm of
q and k over each head, multimodal RoPE in three sections (text sets the
streams equal), a sparse SwiGLU feed-forward renormalised over the chosen
experts whose width is `moe_intermediate_size`, and the learned
sparse-attention indexer of `sa_config` (`ops/sparse_attention.py`). The
vision tower is not in the catalog's `config` and is not built. The contract
is benchmark/models/llama.py's.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_keye as counts  # noqa: F401
from benchmark.models import llama as dense

# What the block needs of the program's model description beyond llama's.
NEEDS = ("n_experts", "top_k_experts", "norm_topk_prob", "qk_norm",
         "moe_aux_weight", "head_dim", "mrope_section", "index_topk",
         "index_heads", "index_head_dim")

CHECK_LEAVES = dict(dense.CHECK_LEAVES, router=("layers", "router"),
                    q_norm=("layers", "q_norm"), k_norm=("layers", "k_norm"))

# head_dim is not hidden_size / heads, and top-k (32) is under the
# rehearsal's max_seq (128): `--rehearse` selects.
REHEARSE = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "intermediate_size": 128, "moe_intermediate_size": 32,
    "vocab_size": 256, "num_hidden_layers": 2, "num_experts": 8,
    "num_local_experts": 8, "num_experts_per_tok": 2,
    "rope_scaling": {"mrope_section": [4, 6, 6], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 32}}


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what this block does not compute, instead of running another
    model under Keye's name."""
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
    rope = model.get("rope_scaling") or {}
    sections = rope.get("mrope_section")
    if (rope.get("rope_type", rope.get("type")) != "default" or not sections
            or set(rope) - {"mrope_section", "rope_type", "type"}):
        problems.append("rope_scaling other than the default mrope")
    elif 2 * sum(sections) != model["head_dim"] or len(sections) != 3:
        problems.append("mrope_section is not three sections of head_dim / 2")
    sa = model.get("sa_config") or {}
    if not sa.get("topk"):
        problems.append("sa_config without topk")
    elif sa.get("indexer_num_kv_heads", 1) != 1:
        problems.append("more than one indexer key head")
    if problems:
        raise ValueError("arch 'keye' cannot run this model: "
                         + "; ".join(problems))


def build_config(model: Dict[str, Any], dtypes: Dict[str, str], max_seq: int):
    """Fails here, in the parent before any cluster starts, on a program
    whose model description cannot say what Keye needs."""
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    check_supported(model)
    missing = [f for f in NEEDS
               if f not in {x.name for x in dataclasses.fields(LlamaConfig)}]
    if missing:
        raise ValueError(
            f"arch 'keye' needs LlamaConfig fields {missing}, which this "
            "program's ray_tpu/models/llama.py does not have: it cannot run "
            "Keye's block (a head width of its own, per-head q/k norm, "
            "mrope, the sparse-attention indexer)")
    sa = model["sa_config"]
    kw = {field: model[key] for key, field in dense.KEYS.items()}
    # One expert's width; `intermediate_size` is the dense width no layer has.
    kw["d_ff"] = model["moe_intermediate_size"]
    # moe_aux_weight 0: the reference's loss is cross-entropy alone.
    return LlamaConfig(
        max_seq=int(max_seq), param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]), head_dim=model["head_dim"],
        qk_norm="head", n_experts=model["num_experts"],
        top_k_experts=model["num_experts_per_tok"],
        norm_topk_prob=bool(model.get("norm_topk_prob", False)),
        moe_aux_weight=0.0,
        mrope_section=tuple(model["rope_scaling"]["mrope_section"]),
        index_topk=sa["topk"], index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], **kw)


init_params = dense.init_params
loss_fn = dense.loss_fn


def reference():
    from benchmark import reference_keye
    return reference_keye
