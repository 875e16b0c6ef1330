"""Architecture adapter `mimo`: the published `config.json` keys of
MiMo-V2-Flash (`model_type: mimo_v2_flash`) -> the program's `LlamaConfig`
(ray_tpu/models/llama.py) with what this model adds to the block: two kinds of
ATTENTION in one stack (`hybrid_layer_pattern`: 0 full, 1 window of
`sliding_window` with a learned sink a head), each kind with its own kv heads
and rotary theta, keys of `head_dim` 192 of which RoPE turns the first
`int(192 x partial_rotary_factor)` = 64, values of `v_head_dim` 128, the
attention's output times `attention_value_scale`; `moe_layer_freq` leading
dense layers; the sigmoid router with a selection bias (`noaux_tc`, one
group); and a SHARE of the routed experts: `n_routed_experts` counts the
experts HELD here and `expert_parallel` says which of how many
(`routed_experts_total`, the router's width). The multi-token-prediction
layers are not built. The contract is benchmark/models/llama.py's.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_mimo as counts  # noqa: F401

# What the block needs of the program's model description beyond llama's.
NEEDS = ("attn_pattern", "window", "window_kv_heads", "window_rope_theta",
         "window_sink", "rotary_dim", "value_scale", "v_head_dim",
         "first_dense", "d_ff_dense", "router_score", "experts_held")

# Serving only: the program's training forward refuses mixed attention.
CHECK_LEAVES: Dict[str, Any] = {}

# Widths of the rehearsal: 4 heads of 48 (16 turned, 32 passed) with values of
# 32, 1 kv head in the full layers and 2 in the window layers, a window of 16,
# a dense layer then window, window, full; 16 experts, 4 a token, experts
# 4..7 held here.
REHEARSE = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1,
    "head_dim": 48, "v_head_dim": 32, "swa_num_attention_heads": 4,
    "swa_num_key_value_heads": 2, "swa_head_dim": 48, "swa_v_head_dim": 32,
    "sliding_window": 16, "sliding_window_size": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "vocab_size": 256,
    "num_hidden_layers": 4, "hybrid_layer_pattern": [0, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1], "n_routed_experts": 4,
    "num_experts_per_tok": 4,
    "expert_parallel": {"chips": 4, "rank": 1, "routed_experts_total": 16}}


def rotary_dim(model: Dict[str, Any]) -> int:
    return int(model["head_dim"] * model["partial_rotary_factor"])


def first_dense(model: Dict[str, Any]) -> int:
    """The leading layers whose `moe_layer_freq` is 0."""
    freq = list(model["moe_layer_freq"])
    return freq.index(1) if 1 in freq else len(freq)


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
    if model.get("scoring_func") != "sigmoid" \
            or model.get("topk_method") != "noaux_tc":
        problems.append("a router other than sigmoid scores with noaux_tc")
    if model.get("n_group", 1) != 1 or model.get("topk_group", 1) != 1:
        problems.append("a group-limited router (n_group, topk_group): this "
                        "block's reference chooses among all experts")
    if model.get("n_shared_experts"):
        problems.append("shared experts (n_shared_experts)")
    for swa, full in (("swa_num_attention_heads", "num_attention_heads"),
                      ("swa_head_dim", "head_dim"),
                      ("swa_v_head_dim", "v_head_dim")):
        if model.get(swa, model[full]) != model[full]:
            problems.append(f"{swa} differs from {full}: one query-head "
                            "count and one pair of head widths a stack")
    if model.get("add_full_attention_sink_bias"):
        problems.append("a sink on the full-attention layers "
                        "(add_full_attention_sink_bias)")
    if model.get("sliding_window_size", model["sliding_window"]) \
            != model["sliding_window"]:
        problems.append("sliding_window_size differs from sliding_window")
    layers = model["num_hidden_layers"]
    pattern, freq = (list(model.get(k) or ())
                     for k in ("hybrid_layer_pattern", "moe_layer_freq"))
    if len(pattern) != layers or len(freq) != layers \
            or set(pattern) - {0, 1} or set(freq) - {0, 1}:
        problems.append("hybrid_layer_pattern and moe_layer_freq: one of 0 "
                        "or 1 for each of num_hidden_layers layers")
    else:
        dense = first_dense(model)
        if not 0 < dense < layers or 0 in freq[dense:]:
            problems.append("moe_layer_freq: leading dense layers, then "
                            "sparse ones only")
        elif any(pattern[:dense]):
            problems.append("a leading dense layer with window attention")
    r = rotary_dim(model)
    if r % 2 or not 0 < r < model["head_dim"]:
        problems.append("partial_rotary_factor: an even part of head_dim, "
                        "neither none of it nor all")
    ep = model.get("expert_parallel") or {}
    total = ep.get("routed_experts_total", 0)
    held = model["n_routed_experts"]
    if not total or total % held or ep.get("chips") != total // held \
            or not 0 <= ep.get("rank", -1) < total // held:
        problems.append("expert_parallel does not say which n_routed_experts "
                        "of how many are held (chips, rank, "
                        "routed_experts_total)")
    elif model["num_experts_per_tok"] > total:
        problems.append("num_experts_per_tok exceeds the experts")
    if problems:
        raise ValueError("arch 'mimo' cannot run this model: "
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
            f"arch 'mimo' needs LlamaConfig fields {missing}, which this "
            "program's ray_tpu/models/llama.py does not have: it cannot run "
            "this block (window beside full attention on other kv heads, a "
            "sink, keys wider than values, a partial rotation, a value "
            "scale, a share of the experts outside latent attention)")
    ep, held = model["expert_parallel"], model["n_routed_experts"]
    # d_ff: one expert's width; moe_aux_weight 0: serving takes no loss.
    return LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        v_head_dim=model["v_head_dim"], d_ff=model["moe_intermediate_size"],
        max_seq=int(max_seq), rope_theta=float(model["rope_theta"]),
        norm_eps=model["layernorm_epsilon"],
        param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]),
        attn_pattern=tuple(model["hybrid_layer_pattern"]),
        window=model["sliding_window"],
        window_kv_heads=model["swa_num_key_value_heads"],
        window_rope_theta=float(model["swa_rope_theta"]),
        window_sink=bool(model["add_swa_attention_sink_bias"]),
        rotary_dim=rotary_dim(model),
        value_scale=float(model["attention_value_scale"]),
        first_dense=first_dense(model), d_ff_dense=model["intermediate_size"],
        n_experts=ep["routed_experts_total"],
        top_k_experts=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]), moe_aux_weight=0.0,
        router_score="sigmoid", n_group=1, topk_group=1,
        routed_scale=float(model.get("routed_scaling_factor") or 1.0),
        experts_held=(ep["rank"] * held, held))


# What a router's selection bias is balanced on (`init_params`).
BALANCE_SEQUENCES = 64
SEQUENCE_TOKENS = 1024


def init_params(cfg, seed: int):
    """Weights on the device from the seed, as every adapter's, and then the
    routers' selection bias BALANCED by the rule that trains it, the
    one `benchmark/models/dots.py::init_params` uses and says why (its step
    count and its choice function, imported): a share's timing follows its
    routing, and with a bias left as drawn this chip's 16 experts meet
    5.2-6.4% of the assignments where a deployment's share is one sixteenth
    (PERF.md, PR 39). Each sparse layer's bias, in the order the layers run,
    is moved until every expert meets as many as any other of the
    assignments of BALANCE_SEQUENCES sequences of SEQUENCE_TOKENS seeded ids
    each, taken through this block's reference layers with the bias already
    found for the layers before.

    MANY sequences, not one long one. With seeded weights every attention
    layer averages away what a sequence's tokens hold apart and keeps what
    they hold in common, so the rows a router sees share a direction that
    is the SEQUENCE's, and every sequence has its own popular experts: a
    bias balanced on ONE sequence of 4,096 ids (every expert 123-132 of its
    tokens) met 6-493 tokens an expert on another sequence as long (my CPU
    probe at the cell's size, PR 42). What such a bias had fitted of its one
    sequence stayed in it as a constant error on every prompt served: this
    chip's share read 5.83-6.66% by the seed and the cell's rate followed it
    by 2.3% (PERF.md section 6, PR 42). Balanced over the SUM of K
    sequences, one sequence's own direction counts for a K-th: on the chip
    (my probe, PR 42, four seeds, fresh prompts of 6,144 and 8,064 ids) a
    layer's share lay 0.77 from 6.25 (rms) at K = 1, 0.33 at 8 and 0.22 at
    32, of which 0.23 is the probe's own six prompts; sequences of 1,024
    read what sequences of 4,096 and of 8,064 read, at a quarter of the
    cost, which is the rule's 400 top-8 choices over every row: 11.5 s for
    32 x 1,024 rows, 70 s for 32 x 4,096. The matmuls run at the device's
    default precision: what is balanced is a distribution, not a value."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_mimo as ref
    from benchmark.models import llama as dense
    from benchmark.models.dots import BALANCE_STEPS
    from benchmark.reference_dots import combine_from_scores
    params = dense.init_params(cfg, seed)
    m = _model_of(cfg)
    (_, _), total = ref.held_experts(m)
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
                                 jnp.zeros(total, jnp.float32))

    attention = jax.jit(lambda x, lp, window: ref.attention_half(
        x, lp, m, window), static_argnames="window")
    scores = jax.jit(lambda x, w, router: jax.nn.sigmoid(
        ref._rms_norm(x, w, eps) @ router.astype(jnp.float32)))
    feed = jax.jit(lambda x, lp, i: ref.feed_forward_half(x, lp, m, i))
    xs = [params["embed"][row].astype(jnp.float32) for row in ids]
    for name, i, window in ref.stack_order(m):
        stack = params[name]
        sparse = "router" in stack
        lp = {n: v if sparse and n in ref._EXPERTS else v[i]
              for n, v in stack.items()}
        xs = [attention(x, lp, window=window) for x in xs]
        if sparse:
            bias = balance(jnp.concatenate(
                [scores(x, lp["mlp_norm"], lp["router"]) for x in xs]))
            lp["router_bias"] = bias
            stack["router_bias"] = stack["router_bias"].at[i].set(
                bias.astype(stack["router_bias"].dtype))
        xs = [feed(x, lp, i if sparse else None) for x in xs]
    return params


def _model_of(cfg):
    """The published keys `reference_mimo` reads, back from the program's
    config (`build_config` undone)."""
    offset, held = cfg.experts_held
    return {
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "v_head_dim": cfg.v_head_dim,
        # any factor that int(head_dim x factor) gives rotary_dim back from
        "partial_rotary_factor": (cfg.rotary_dim + 0.5) / cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "swa_num_key_value_heads": cfg.window_kv_heads,
        "swa_rope_theta": cfg.window_rope_theta,
        "sliding_window": cfg.window,
        "add_swa_attention_sink_bias": cfg.window_sink,
        "add_full_attention_sink_bias": False,
        "attention_value_scale": cfg.value_scale,
        "layernorm_epsilon": cfg.norm_eps,
        "hybrid_layer_pattern": list(cfg.attn_pattern),
        "moe_layer_freq": [int(i >= cfg.first_dense)
                           for i in range(cfg.n_layers)],
        "num_experts_per_tok": cfg.top_k_experts,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scale, "n_routed_experts": held,
        "expert_parallel": {"rank": offset // held,
                            "routed_experts_total": cfg.n_experts}}


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)   # refuses, by name


def reference():
    from benchmark import reference_mimo
    return reference_mimo
