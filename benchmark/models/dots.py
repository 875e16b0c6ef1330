"""Architecture adapter `dots`: the published `config.json` keys of
dots.vlm1.inst's language model (`model_type: dots_vlm`; every key of the
block is a `DeepseekV3Config` key) -> the program's `LlamaConfig`
(ray_tpu/models/llama.py) with what this model adds to that block: latent
attention (MLA: q through a latent of `q_lora_rank`, one latent row of
`kv_lora_rank` and one rotary key of `qk_rope_head_dim` a token, keys and
values up-projected a head), YaRN frequencies and its factor on the softmax
scale, `first_k_dense_replace` leading dense layers, a shared expert, the
sigmoid router with a selection bias and group-limited top-k, and a SHARE of
the routed experts: `n_routed_experts` counts the experts HELD here and
`expert_parallel` says which of how many (`routed_experts_total`, the
router's width). The vision tower and the multi-token-prediction module are
not built. The contract is benchmark/models/llama.py's.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_dots as counts  # noqa: F401

# What the block needs of the program's model description beyond llama's.
NEEDS = ("kv_lora_rank", "q_lora_rank", "qk_nope_dim", "qk_rope_dim",
         "v_head_dim", "rope_yarn", "first_dense", "d_ff_dense",
         "n_shared_experts", "router_score", "n_group", "topk_group",
         "routed_scale", "experts_held")

# Serving only: the program's training forward refuses latent attention.
CHECK_LEAVES: Dict[str, Any] = {}

# Widths of the rehearsal: 4 heads of 16 + 8, latents of 48 and 32, 16
# experts in 4 groups of which 2 stay, 4 a token, experts 4..7 held here.
REHEARSE = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "vocab_size": 256, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "n_routed_experts": 4, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4,
    "expert_parallel": {"chips": 4, "rank": 1, "routed_experts_total": 16},
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"}}


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
    if model.get("num_nextn_predict_layers"):
        problems.append("a multi-token-prediction module "
                        "(num_nextn_predict_layers): one token a slot a step")
    if model.get("moe_layer_freq", 1) != 1:
        problems.append("dense layers among the sparse ones (moe_layer_freq)")
    if model.get("scoring_func") != "sigmoid" \
            or model.get("topk_method") != "noaux_tc":
        problems.append("a router other than sigmoid scores with noaux_tc")
    if not model.get("q_lora_rank"):
        problems.append("q without a latent (q_lora_rank)")
    if model.get("num_key_value_heads") != model["num_attention_heads"]:
        problems.append("num_key_value_heads differs from the heads: latent "
                        "attention up-projects a key and a value a head")
    rope = model.get("rope_scaling") or {}
    if rope.get("type", rope.get("rope_type")) != "yarn":
        problems.append("rope_scaling other than yarn")
    elif rope.get("mscale") != rope.get("mscale_all_dim"):
        problems.append("yarn's mscale differs from mscale_all_dim: the "
                        "rotary tables would carry a factor")
    ep = model.get("expert_parallel") or {}
    total = ep.get("routed_experts_total", 0)
    held = model["n_routed_experts"]
    if not total or total % held or ep.get("chips") != total // held \
            or not 0 <= ep.get("rank", -1) < total // held:
        problems.append("expert_parallel does not say which n_routed_experts "
                        "of how many are held (chips, rank, "
                        "routed_experts_total)")
    elif total % model["n_group"] \
            or not 0 < model["topk_group"] <= model["n_group"] \
            or model["num_experts_per_tok"] > total // model["n_group"] \
            * model["topk_group"]:
        problems.append("n_group / topk_group do not divide the experts")
    if not 0 < model["first_k_dense_replace"] < model["num_hidden_layers"]:
        problems.append("first_k_dense_replace outside 1..layers - 1")
    if problems:
        raise ValueError("arch 'dots' cannot run this model: "
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
            f"arch 'dots' needs LlamaConfig fields {missing}, which this "
            "program's ray_tpu/models/llama.py does not have: it cannot run "
            "this block (latent attention, YaRN, leading dense layers, a "
            "shared expert, the sigmoid group-limited router, a share of "
            "the experts)")
    rope, ep = model["rope_scaling"], model["expert_parallel"]
    held = model["n_routed_experts"]
    # d_ff: one expert's width; moe_aux_weight 0: serving takes no loss.
    return LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["moe_intermediate_size"], max_seq=int(max_seq),
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]),
        kv_lora_rank=model["kv_lora_rank"], q_lora_rank=model["q_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        rope_yarn=(float(rope["factor"]),
                   int(rope["original_max_position_embeddings"]),
                   float(rope["beta_fast"]), float(rope["beta_slow"]),
                   float(rope["mscale_all_dim"])),
        first_dense=model["first_k_dense_replace"],
        d_ff_dense=model["intermediate_size"],
        n_shared_experts=model["n_shared_experts"],
        n_experts=ep["routed_experts_total"],
        top_k_experts=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]), moe_aux_weight=0.0,
        router_score="sigmoid", n_group=model["n_group"],
        topk_group=model["topk_group"],
        routed_scale=float(model["routed_scaling_factor"]),
        experts_held=(ep["rank"] * held, held))


# The selection bias is balanced on this many tokens of seeded ids, by this
# many steps of the rule that trains it (below).
BALANCE_TOKENS = 4096
BALANCE_STEPS = 400


def init_params(cfg, seed: int):
    """Weights on the device from the seed, as every adapter's, and then the
    routers' selection bias (`e_score_correction_bias`) BALANCED, as the
    published model's is: training moves that buffer, and nothing else, until
    every expert meets as many tokens as any other (DeepSeek-V3's
    auxiliary-loss-free balancing: after a batch, b_e goes up a step where
    expert e was under the mean load and down where it was over). With
    seeded weights and a bias drawn at random, the experts' loads follow the
    draw: this chip's 16 experts met 5.2% to 6.4% of the assignments on as
    many seeds, and the cell's rate spread by 2.3% with them (PERF.md, PR
    39), where a deployment's share is one sixteenth by construction. So the
    bias of each sparse layer, in the order the layers run, is moved by that
    same rule (a step that halves every 50 updates) on BALANCE_TOKENS seeded
    ids under the vocabulary, through the plain reference's float32 layers
    (`reference_dots.attention_half`, `feed_forward_half`, with the bias
    already found for the layers before). The bias chooses and does not
    weigh, so this changes WHICH experts a token meets and no weight."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_dots as ref
    from benchmark.models import llama as dense
    params = dense.init_params(cfg, seed)
    if "router_bias" not in params["layers"]:
        return params
    m = _model_of(cfg)
    (_, _), total = ref.held_experts(m)
    k, groups, kept = cfg.top_k_experts, cfg.n_group, cfg.topk_group
    key = jax.random.PRNGKey((int(seed) * 7919 + 1) % (2 ** 31 - 1))
    tokens = jax.random.randint(key, (min(BALANCE_TOKENS, cfg.max_seq),), 0,
                                cfg.vocab_size)

    @jax.jit
    def balance(g, router):
        s = jax.nn.sigmoid(g @ router.astype(jnp.float32))

        def step(i, bias):
            chosen = ref.combine_from_scores(s, bias, k, groups, kept, True,
                                             1.0) > 0
            load = jnp.sum(chosen, axis=0)
            rate = 0.02 * 0.5 ** (i // 50)
            return bias + rate * jnp.sign(jnp.mean(load) - load)

        return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                                 jnp.zeros(total, jnp.float32))

    attention = jax.jit(lambda x, lp: ref.attention_half(x, lp, m))
    normed = jax.jit(lambda x, w: ref._rms_norm(x, w, m["rms_norm_eps"]))
    feed = jax.jit(lambda x, lp, i: ref.feed_forward_half(x, lp, m, i))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for name, i in ref._stack(params):
            stack = params[name]
            sparse = "router" in stack
            lp = {n: v if sparse and n in ref._EXPERTS else v[i]
                  for n, v in stack.items()}
            x = attention(x, lp)
            if sparse:
                bias = balance(normed(x, lp["mlp_norm"]), lp["router"])
                lp["router_bias"] = bias
                stack["router_bias"] = stack["router_bias"].at[i].set(
                    bias.astype(stack["router_bias"].dtype))
            x = feed(x, lp, i if sparse else None)
    return params


def _model_of(cfg):
    """The published keys `reference_dots` reads, back from the program's
    config (`build_config` undone)."""
    factor, orig, fast, slow, mscale = cfg.rope_yarn
    offset, held = cfg.experts_held
    return {
        "num_attention_heads": cfg.n_heads,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {"factor": factor, "beta_fast": fast,
                         "beta_slow": slow, "mscale_all_dim": mscale,
                         "original_max_position_embeddings": orig},
        "num_experts_per_tok": cfg.top_k_experts, "n_group": cfg.n_group,
        "topk_group": cfg.topk_group, "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scale, "n_routed_experts": held,
        "expert_parallel": {"rank": offset // held,
                            "routed_experts_total": cfg.n_experts}}


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)   # refuses, by name


def reference():
    from benchmark import reference_dots
    return reference_dots
