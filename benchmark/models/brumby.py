"""Architecture adapter `brumby`: the published `config.json` keys of
Brumby-14B-Base (`model_type: brumby`: key for key a Qwen3-14B dense decoder)
-> the program's `LlamaConfig` (ray_tpu/models/llama.py) with what this model
adds to the block: POWER RETENTION of degree 2 in every layer (`mixer`
"retention": the Qwen3 block's q, k, v, per-head q/k norm and RoPE kept, a
gate a kv head, no softmax and NO K and V cache: a slot holds a recurrent
state of fixed size a layer), a dense SwiGLU, an untied head. The degree, the
gate's form and the normalisation are no keys of config.json: the
configuration file lists them under `assumed` and carries the degree as
`retention_degree`. The contract is benchmark/models/llama.py's. Serve only:
the program's training forward refuses power retention by name, so `loss_fn`
does.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_brumby as counts  # noqa: F401
from benchmark.models import llama as dense

# What the block needs of the program's model description beyond llama's.
NEEDS = ("mixer", "retention_degree", "qk_norm", "head_dim")

# Serving only.
CHECK_LEAVES: Dict[str, Any] = {}

# Widths of the rehearsal: 4 query heads on 2 kv heads of 16 (an expansion of
# 136 numbers a head), 2 layers.
REHEARSE = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "intermediate_size": 128, "vocab_size": 256,
            "num_hidden_layers": 2}


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what this block does not compute, instead of running another
    model under Brumby's name."""
    problems = []
    if model.get("sliding_window") is not None \
            or model.get("use_sliding_window"):
        problems.append("a sliding window")
    if model.get("tie_word_embeddings"):
        problems.append("tied embeddings")
    if model.get("hidden_act", "silu") != "silu":
        problems.append(f"hidden_act {model.get('hidden_act')!r}")
    if model.get("attention_bias"):
        problems.append("attention_bias")
    if model.get("rope_scaling"):
        problems.append("rope_scaling")
    if model.get("retention_degree", 2) != 2:
        problems.append("a retention degree other than 2")
    if not model.get("head_dim") or model["head_dim"] % 2:
        problems.append("no even head_dim")
    if model["num_attention_heads"] % model["num_key_value_heads"]:
        problems.append("query heads that are not whole groups a kv head")
    if problems:
        raise ValueError("arch 'brumby' cannot run this model: "
                         + "; ".join(problems))


def build_config(model: Dict[str, Any], dtypes: Dict[str, str], max_seq: int):
    """Fails here, in the parent before any cluster starts, on a program
    whose model description cannot say what Brumby needs."""
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    check_supported(model)
    missing = [f for f in NEEDS
               if f not in {x.name for x in dataclasses.fields(LlamaConfig)}]
    if missing:
        raise ValueError(
            f"arch 'brumby' needs LlamaConfig fields {missing}, which this "
            "program's ray_tpu/models/llama.py does not have: it cannot run "
            "power retention (a gate a kv head, a recurrent state a slot "
            "and no K and V cache)")
    kw = {field: model[key] for key, field in dense.KEYS.items()}
    return LlamaConfig(
        max_seq=int(max_seq), param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]), head_dim=model["head_dim"],
        qk_norm="head", mixer="retention",
        retention_degree=int(model.get("retention_degree", 2)), **kw)


# Normal at 0.02 for every matrix (`wg` too), the norms' weights 1, and `bg` so
# that a kv head's half-life is log-uniform in 16..4,096 positions: the
# program's own initialisation (`llama.init_params`), from the run's seed.
init_params = dense.init_params


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)   # refuses, by name


def reference():
    from benchmark import reference_brumby
    return reference_brumby
