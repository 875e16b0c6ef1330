"""Architecture adapter `minicpm_sala`: the published `config.json` keys of
MiniCPM-SALA (`model_type: minicpm_sala`) -> the program's `LlamaConfig`
(ray_tpu/models/llama.py) with what this model adds to the block: two kinds of
MIXER in one stack in no period (`mixer_types`: `minicpm4`, grouped-query
attention with no rotation that reads whole contexts under `dense_len` and
from there on the `topk` best BLOCKS by a score against mean-pooled keys, one
selection a kv head, `sparse_config`; `lightning-attn`, decayed linear
attention over `lightning_nh` heads with a constant decay a head and a
layer, a norm a head of q and k, RoPE, a norm and a gate on its output); a
sigmoid gate on the sparse kind's output; and the family's three scalar
multipliers (`scale_emb` on the embedding, `scale_depth / sqrt(depth)` on
every branch, `dim_model_base / hidden_size` on the logits), where `depth` is
the PUBLISHED one: `mixer_types` is kept whole, read at its first
`num_hidden_layers` entries, and its length is the depth the decay and the
branch's scale are published for. The contract is benchmark/models/llama.py's.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_minicpm_sala as counts  # noqa: F401

# What the block needs of the program's model description beyond llama's.
NEEDS = ("mixer_types", "published_layers", "lightning_heads",
         "lightning_head_dim", "lightning_qk_norm", "lightning_rope",
         "lightning_gate", "lightning_norm", "sparse_gate", "sparse_kernel",
         "sparse_stride", "sparse_block", "sparse_topk", "sparse_init_blocks",
         "sparse_window", "dense_len", "embed_scale", "residual_scale",
         "logit_scale", "rope")

# Serving only: the program's training forward refuses this stack.
CHECK_LEAVES: Dict[str, Any] = {}

SPARSE, LINEAR = counts.SPARSE, counts.LINEAR

# Widths of the rehearsal: heads of 16, 4 query heads on 2 kv heads in the
# sparse kind and 4 heads in the linear kind; a block is the engine's page,
# which the rehearsal leaves at 64: the published kernel and stride, both of
# max_seq 128's two blocks a query (the first and the window's one), whole
# contexts under 64. The published order's first four layers.
REHEARSE = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
    "num_hidden_layers": 4, "lightning_nh": 4, "lightning_nkv": 4,
    "lightning_head_dim": 16, "dim_model_base": 16,
    "sparse_config": {"kernel_size": 32, "kernel_stride": 16,
                      "block_size": 64, "topk": 2, "init_blocks": 1,
                      "window_size": 64, "dense_len": 64}}

per_layer = counts.per_layer
sparse_sizes = counts.sparse_sizes


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
    kinds = model.get("mixer_types") or []
    if len(kinds) < model["num_hidden_layers"] \
            or set(kinds) - {SPARSE, LINEAR}:
        problems.append("mixer_types: 'minicpm4' or 'lightning-attn' for "
                        "each of the published layers, num_hidden_layers of "
                        "them at least")
    if model.get("attn_use_rope"):
        problems.append("attn_use_rope: the sparse layers take no rotation")
    if model.get("lightning_nkv") != model.get("lightning_nh"):
        problems.append("lightning_nkv differs from lightning_nh (a linear "
                        "layer's state is a query head's own)")
    if model.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)":
        problems.append(f"lightning_scale {model.get('lightning_scale')!r}")
    if model["num_attention_heads"] % model["num_key_value_heads"]:
        problems.append("num_attention_heads: whole groups a kv head")
    for key in ("scale_emb", "scale_depth", "dim_model_base"):
        if not model.get(key):
            problems.append(f"{key} is not stated")
    if problems:
        raise ValueError("arch 'minicpm_sala' cannot run this model: "
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
            f"arch 'minicpm_sala' needs LlamaConfig fields {missing}, which "
            "this program's ray_tpu/models/llama.py does not have: it cannot "
            "run this block (a mixer by layer in no period, decayed linear "
            "attention with a state a slot, attention that selects blocks "
            "against pooled keys, gates and a norm on their outputs)")
    sizes = sparse_sizes(model)
    depth = len(model["mixer_types"])
    return LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], max_seq=int(max_seq),
        rope_theta=float(model["rope_theta"]), norm_eps=model["rms_norm_eps"],
        param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]),
        rope=False, mixer_types=tuple(per_layer(model)),
        published_layers=depth,
        lightning_heads=model["lightning_nh"],
        lightning_head_dim=model["lightning_head_dim"],
        lightning_qk_norm=bool(model.get("qk_norm")),
        lightning_rope=bool(model.get("lightning_use_rope")),
        lightning_gate=bool(model.get("use_output_gate")),
        lightning_norm=bool(model.get("use_output_norm")),
        sparse_gate=bool(model.get("attn_use_output_gate")),
        sparse_kernel=sizes["kernel_size"], sparse_stride=sizes["kernel_stride"],
        sparse_block=sizes["block_size"], sparse_topk=sizes["topk"],
        sparse_init_blocks=sizes["init_blocks"],
        sparse_window=sizes["window_size"], dense_len=sizes["dense_len"],
        embed_scale=float(model["scale_emb"]),
        residual_scale=float(model["scale_depth"]) / depth ** 0.5,
        logit_scale=float(model["dim_model_base"]) / model["hidden_size"])


def init_params(cfg, seed: int):
    """Weights on the device from the seed, as every adapter's."""
    from benchmark.models import llama as dense
    return dense.init_params(cfg, seed)


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)   # refuses, by name


def reference():
    from benchmark import reference_minicpm_sala
    return reference_minicpm_sala
