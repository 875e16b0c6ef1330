"""Architecture adapter `lfm2`: the published `config.json` keys of
LFM2-24B-A2B (`model_type: lfm2_moe`) -> the program's `LlamaConfig`
(ray_tpu/models/llama.py) with what this model adds to the block: a gated
short convolution of `conv_L_cache` taps in the place of attention wherever
`layer_types` says "conv" (no position signal, a slot keeps the convolution's
last two inputs and nothing else); grouped-query attention on heads of
`hidden_size / num_attention_heads` = 64 in the other layers, an RMS norm over
each head of q and k before RoPE; `num_dense_layers` leading layers with a
dense SwiGLU of `intermediate_size`, then `num_experts` experts of
`moe_intermediate_size` behind a sigmoid router whose `expert_bias` chooses
and does not weigh, the weights renormalised over their sum + 1e-6; a head
tied to the embedding. Every expert is held. The contract is
benchmark/models/llama.py's. Serve only: the program's training forward
refuses the stack by name, so `loss_fn` does.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_lfm2 as counts  # noqa: F401

# What the block needs of the program's model description beyond llama's.
NEEDS = ("conv_layers", "conv_taps", "router_norm_eps", "first_dense",
         "d_ff_dense", "router_score", "tie_embeddings", "qk_norm")

# Serving only: the program's training forward refuses conv layers.
CHECK_LEAVES: Dict[str, Any] = {}

# Widths of the rehearsal: 4 query heads on 2 kv heads of 64 (the head's
# width is the model's own: the arena packs two of them to a row), a dense
# conv layer, then attention, conv, conv: 8 experts, 4 a token.
REHEARSE = {
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 192, "moe_intermediate_size": 128, "vocab_size": 256,
    "num_hidden_layers": 4, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "num_experts": 8, "num_experts_per_tok": 4}


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what this block does not compute, instead of running another
    model under this one's name."""
    problems = []
    if model.get("hidden_act", "silu") != "silu":
        problems.append(f"hidden_act {model.get('hidden_act')!r}")
    if model.get("conv_bias"):
        problems.append("a convolution with bias (conv_bias)")
    if model.get("tie_word_embeddings") is False \
            or model.get("tie_embedding") is False:
        problems.append("an untied head")
    if (model.get("rope_parameters") or {}).get("rope_type",
                                                "default") != "default":
        problems.append("rope scaling (rope_parameters.rope_type)")
    if model.get("head_dim") not in (None, model["hidden_size"]
                                     // model["num_attention_heads"]):
        problems.append("head_dim differs from hidden_size / heads")
    layers = model["num_hidden_layers"]
    types = list(model.get("layer_types") or ())
    if len(types) != layers or set(types) - {"conv", "full_attention"}:
        problems.append("layer_types: one of 'conv' or 'full_attention' for "
                        "each of num_hidden_layers layers")
    else:
        dense = model.get("num_dense_layers", 0)
        if not 0 <= dense < layers:
            problems.append("num_dense_layers: leading dense layers, then "
                            "sparse ones")
        elif "full_attention" in types[:dense]:
            problems.append("a leading dense layer with attention")
        if "conv" not in types or "full_attention" not in types:
            problems.append("layer_types: both kinds of layer")
    if model.get("num_experts", 0) < 1 \
            or not 0 < model.get("num_experts_per_tok", 0) <= model.get(
                "num_experts", 0):
        problems.append("num_experts and num_experts_per_tok: a sparse "
                        "feed-forward after the dense layers")
    if not model.get("use_expert_bias", True):
        problems.append("a router without expert_bias (use_expert_bias "
                        "false): this block's router chooses on s + bias")
    if problems:
        raise ValueError("arch 'lfm2' cannot run this model: "
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
            f"arch 'lfm2' needs LlamaConfig fields {missing}, which this "
            "program's ray_tpu/models/llama.py does not have: it cannot run "
            "this stack (gated short-convolution layers beside attention, "
            "sparse experts behind a sigmoid router in a hybrid stack, the "
            "router's 1e-6)")
    # d_ff: one expert's width; moe_aux_weight 0: serving takes no loss.
    return LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["moe_intermediate_size"], max_seq=int(max_seq),
        rope_theta=float(model["rope_parameters"]["rope_theta"]),
        norm_eps=model["norm_eps"], param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]), qk_norm="head",
        tie_embeddings=True,
        conv_layers=tuple(i for i, kind in enumerate(model["layer_types"])
                          if kind == "conv"),
        conv_taps=model["conv_L_cache"],
        first_dense=model["num_dense_layers"],
        d_ff_dense=model["intermediate_size"],
        n_experts=model["num_experts"],
        top_k_experts=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]), moe_aux_weight=0.0,
        router_score="sigmoid", n_group=1, topk_group=1,
        routed_scale=float(model.get("routed_scaling_factor") or 1.0),
        router_norm_eps=1e-6)


def init_params(cfg, seed: int):
    """Weights on the device from the seed, as every adapter's, and then the
    routers' selection bias (`expert_bias`) BALANCED as training balances it,
    by the rule `benchmark/models/mimo.py::init_params` uses and says why
    (its step count, its choice function and its 64 sequences of 1,024
    seeded ids, imported): over the SUM of the sequences' assignments, taken
    through this block's reference layers with the bias already found for
    the layers before. A bias left as drawn would read loads no
    trained model has; one balanced on a single sequence keeps that
    sequence's popular experts as a constant error on every prompt served
    (PERF.md section 6, PR 42)."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_lfm2 as ref
    from benchmark.models import llama as dense
    from benchmark.models.dots import BALANCE_STEPS
    from benchmark.models.mimo import BALANCE_SEQUENCES, SEQUENCE_TOKENS
    from benchmark.reference_dots import combine_from_scores
    params = dense.init_params(cfg, seed)
    m = _model_of(cfg)
    k, eps = cfg.top_k_experts, cfg.norm_eps
    key = jax.random.PRNGKey((int(seed) * 7919 + 1) % (2 ** 31 - 1))
    ids = jax.random.randint(
        key, (BALANCE_SEQUENCES, min(SEQUENCE_TOKENS, cfg.max_seq)), 0,
        cfg.vocab_size)

    @jax.jit
    def balance(s):
        def step(i, bias):
            chosen = combine_from_scores(s, bias, k, 1, 1, True, 1.0) > 0
            load = jnp.sum(chosen, axis=0)
            rate = 0.02 * 0.5 ** (i // 50)
            return bias + rate * jnp.sign(jnp.mean(load) - load)

        return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                                 jnp.zeros(cfg.n_experts, jnp.float32))

    operator = jax.jit(lambda x, lp, conv: ref.operator_half(x, lp, m, conv),
                       static_argnames="conv")
    scores = jax.jit(lambda x, w, router: jax.nn.sigmoid(
        ref._rms_norm(x, w, eps) @ router.astype(jnp.float32)))
    feed = jax.jit(lambda x, lp, i: ref.feed_forward_half(x, lp, m, i)[0])
    xs = [params["embed"][row].astype(jnp.float32) for row in ids]
    for name, i, conv in ref.stack_order(m):
        stack = params[name]
        sparse = "router" in stack
        lp = {n: v if sparse and n in ref._EXPERTS else v[i]
              for n, v in stack.items()}
        xs = [operator(x, lp, conv=conv) for x in xs]
        if sparse:
            bias = balance(jnp.concatenate(
                [scores(x, lp["mlp_norm"], lp["router"]) for x in xs]))
            lp["router_bias"] = bias
            stack["router_bias"] = stack["router_bias"].at[i].set(
                bias.astype(stack["router_bias"].dtype))
        xs = [feed(x, lp, i if sparse else None) for x in xs]
    return params


def _model_of(cfg):
    """The published keys `reference_lfm2` reads, back from the program's
    config (`build_config` undone)."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "norm_eps": cfg.norm_eps,
        "conv_L_cache": cfg.conv_taps,
        "layer_types": ["conv" if i in cfg.conv_layers else "full_attention"
                        for i in range(cfg.n_layers)],
        "num_hidden_layers": cfg.n_layers,
        "num_dense_layers": cfg.first_dense, "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.top_k_experts,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scale, "use_expert_bias": True,
        "rope_parameters": {"rope_theta": cfg.rope_theta}}


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)   # refuses, by name


def reference():
    from benchmark import reference_lfm2
    return reference_lfm2
