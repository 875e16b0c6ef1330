"""Architecture adapter `llama`: published `config.json` keys -> the kwargs of
the program's `LlamaConfig` (ray_tpu/models/llama.py), which is the one block
this repo runs: pre-norm, rotary (split-half), grouped-query attention,
SwiGLU, untied head, no biases, no sliding window.

A configuration file names its adapter under `arch`. An adapter is a module
here with `to_model_kwargs(model, dtypes, max_seq)`, `init_params` and
`check_supported`; a later architecture is a new file.
"""

from __future__ import annotations

from typing import Any, Dict

KEYS = {  # published key -> LlamaConfig field
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what the block cannot express, instead of running another
    model under this one's name."""
    problems = []
    if model.get("sliding_window") is not None:
        problems.append("sliding_window is set")
    if model.get("tie_word_embeddings"):
        problems.append("tied embeddings")
    if model.get("hidden_act", "silu") != "silu":
        problems.append(f"hidden_act {model.get('hidden_act')!r}")
    hd = model.get("head_dim")
    if hd and hd != model["hidden_size"] // model["num_attention_heads"]:
        problems.append("head_dim differs from hidden_size / heads")
    if problems:
        raise ValueError("arch 'llama' cannot run this model: "
                         + "; ".join(problems))


def to_model_kwargs(model: Dict[str, Any], dtypes: Dict[str, str],
                    max_seq: int) -> Dict[str, Any]:
    """LlamaConfig kwargs. Only what the configuration states is set: every
    other field keeps the program's default, so a changed default is seen."""
    import jax.numpy as jnp

    check_supported(model)
    kw = {field: model[key] for key, field in KEYS.items()}
    kw["max_seq"] = int(max_seq)
    kw["param_dtype"] = jnp.dtype(dtypes["params"])
    kw["dtype"] = jnp.dtype(dtypes["activations"])
    return kw


def build_config(model: Dict[str, Any], dtypes: Dict[str, str], max_seq: int):
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(**to_model_kwargs(model, dtypes, max_seq))


def init_params(cfg, seed: int):
    """Weights on the device, in the type they are used in, in one jitted
    call from the seed."""
    import jax

    from ray_tpu.models.llama import init_params as _init
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    return jax.jit(lambda k: _init(cfg, k))(key)
