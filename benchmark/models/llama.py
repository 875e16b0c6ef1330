"""Architecture adapter `llama`: published `config.json` keys -> the kwargs of
the program's `LlamaConfig` (ray_tpu/models/llama.py), which is the one block
this repo runs: pre-norm, rotary (split-half), grouped-query attention,
SwiGLU, untied head, no biases, no sliding window.

A configuration file names its adapter under `arch`, and the adapter is the
one place the harness learns anything that depends on the architecture. A
later architecture is a new file here with the same names:

  check_supported, build_config, init_params   the program's model, from the
                                               published keys and the seed
  reference()    the module with the plain float32 forward of this block:
                 `served_token_gaps(params, m, prompt, served)` and
                 `loss_and_check_grads(params, m, tokens)`
  loss_fn        the system's side of the train check
  CHECK_LEAVES   name -> path in the parameter tree of each leaf whose
                 gradient the train check compares with the reference's
  counts         the module with `train_flops_per_token(m, seq)`,
                 `prefill_flops(m, n)`, `decode_step_ops_bytes(...)` and
                 `total_params(m)` for this block
  REHEARSE       the tiny widths `--rehearse` runs on the CPU

Importing an adapter imports neither jax nor the program: `run.py`, which
must stay off the chip, reads `REHEARSE` and `counts` from it.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops as counts  # noqa: F401  (the dense block's counts)

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


CHECK_LEAVES = {"final_norm": ("final_norm",),
                "attn_norm": ("layers", "attn_norm"),
                "mlp_norm": ("layers", "mlp_norm")}

REHEARSE = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
            "vocab_size": 256, "num_hidden_layers": 2}


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


def reference():
    from benchmark import reference as ref
    return ref


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)
