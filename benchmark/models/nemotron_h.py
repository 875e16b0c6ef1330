"""Architecture adapter `nemotron_h`: the published `config.json` keys of
NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type: nemotron_h`) -> the program's
`LlamaConfig` (ray_tpu/models/llama.py) with what this model adds to the
block: a stack whose layers are ONE part each, by the letters of
`hybrid_override_pattern` ("M" a Mamba-2 mixer, "E" the experts, "*"
attention; block i is h + part_i(rmsnorm(h))); the mixer's inner width
`mamba_num_heads x mamba_head_dim` (not `expand x hidden_size`) and `n_groups`
groups of B and C; experts of TWO matrices under relu^2
(`mlp_hidden_act`), the shared expert alike; the sigmoid router with a
selection bias (`n_group` 1: no group limit) and `routed_scaling_factor`;
grouped-query attention with NO position signal under a hidden size that is
not heads x head_dim; an untied head; and a SHARE of the routed experts:
`n_routed_experts` counts the experts HELD here and `expert_parallel` says
which of how many (`routed_experts_total`, the router's width). The contract
is benchmark/models/llama.py's. Serve only: the program's training forward
refuses state-space layers by name, so `loss_fn` does.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_nemotron_h as counts  # noqa: F401

# What the block needs of the program's model description beyond llama's.
NEEDS = ("layer_parts", "ssm_groups", "ssm_head_dim", "ffn", "ssm_state",
         "ssm_heads", "ssm_conv", "rope", "n_shared_experts", "experts_held",
         "router_score", "routed_scale")

# Serving only: the program's training forward refuses state-space layers.
CHECK_LEAVES: Dict[str, Any] = {}

# Widths of the rehearsal: 8 Mamba-2 heads of 16 channels on 16 states in 2
# groups, 4 query heads of 32 on 2 kv heads under a hidden size of 96 (not
# heads x head_dim), 8 experts of 64, 3 a token, experts 0..3 held, a shared
# expert of 128; mixer, experts, mixer, attention, experts, mixer.
REHEARSE = {
    "hidden_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "intermediate_size": 64, "moe_intermediate_size": 64,
    "moe_shared_expert_intermediate_size": 128, "vocab_size": 256,
    "num_hidden_layers": 6, "hybrid_override_pattern": "MEM*EM",
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 3,
    "expert_parallel": {"chips": 2, "rank": 0, "routed_experts_total": 8}}

# What the routed experts' W_down is multiplied by after it is drawn at 0.02
# as every matrix is: at 0.02 ONE routed expert's part (2.5 x a sixth of the
# mixture) is a fifth of the stream it is added to, bfloat16 turns a token's
# sixth and seventh expert at the first E layer for 4% of the tokens, each
# turned choice turns others downstream (57% of the tokens at the seventh),
# and the check's gaps are those flips: sound runs read half of what the int8
# control reads. At 0.2 a sound run reads a fifth of the control and the
# routed experts dropped whole still read 6 times the control (PERF.md
# sections 2 and 6 and the configuration's `assumed` have the readings at 1,
# 0.4 and 0.2, my chip runs, PR 55).
ROUTED_DOWN = 0.2

# The sequences a router's bias is balanced on (`init_params`).
BALANCE_SEQUENCES = 32
SEQUENCE_TOKENS = 1024


def check_supported(model: Dict[str, Any]) -> None:
    """Refuse what this block does not compute, instead of running another
    model under this one's name."""
    problems = []
    layers = model["num_hidden_layers"]
    parts = model.get("hybrid_override_pattern") or ""
    if set(parts) - set("ME*"):
        problems.append(
            "hybrid_override_pattern: a letter other than 'M' (a Mamba-2 "
            f"mixer), 'E' (the experts) and '*' (attention): "
            f"{sorted(set(parts) - set('ME*'))}")
    elif len(parts) != layers or not set("ME") <= set(parts):
        problems.append("hybrid_override_pattern: a letter for each of "
                        "num_hidden_layers layers, mixers and experts among "
                        "them")
    if model.get("mlp_hidden_act") != "relu2":
        problems.append(f"mlp_hidden_act {model.get('mlp_hidden_act')!r}: "
                        "the experts are relu(x W_up)^2 W_down")
    if model.get("mamba_hidden_act", "silu") != "silu":
        problems.append(f"mamba_hidden_act {model.get('mamba_hidden_act')!r}")
    if any(model.get(k) for k in ("attention_bias", "mlp_bias",
                                  "mamba_proj_bias", "use_bias")):
        problems.append("a projection bias (attention_bias, mlp_bias, "
                        "mamba_proj_bias, use_bias)")
    if not model.get("use_conv_bias", True):
        problems.append("a convolution without bias")
    if model.get("tie_word_embeddings"):
        problems.append("a tied head")
    if model.get("sliding_window") is not None:
        problems.append("sliding_window is set")
    if tuple(model.get("time_step_limit", (0.0, float("inf")))) \
            != (0.0, float("inf")):
        problems.append("time_step_limit: a clamp on the time step")
    if model.get("norm_eps", model["layer_norm_epsilon"]) \
            != model["layer_norm_epsilon"]:
        problems.append("norm_eps differs from layer_norm_epsilon")
    groups = model.get("n_group", 1)
    if groups > 1 and not 1 <= model.get("topk_group", 0) <= groups:
        problems.append("n_group > 1 without the group limit (topk_group in "
                        "1..n_group)")
    if model.get("mamba_num_heads", 0) % model.get("n_groups", 1):
        problems.append("n_groups does not divide mamba_num_heads")
    width = model.get("moe_intermediate_size", 0)
    shared = model.get("moe_shared_expert_intermediate_size", 0)
    if not width or not shared or shared % width \
            or model.get("n_shared_experts", 1) != 1:
        problems.append("moe_shared_expert_intermediate_size: ONE shared "
                        "expert, a whole number of routed experts wide")
    ep = model.get("expert_parallel")
    held = model.get("n_routed_experts", 0)
    total = ep.get("routed_experts_total", 0) if ep else held
    if ep and (not total or not held or total % held
               or ep.get("chips") != total // held
               or not 0 <= ep.get("rank", -1) < total // held):
        problems.append("expert_parallel does not say which n_routed_experts "
                        "of routed_experts_total are held (chips, rank)")
    if held < 1 or not 0 < model.get("num_experts_per_tok", 0) <= total \
            or (groups > 1 and total % groups):
        problems.append("n_routed_experts and num_experts_per_tok: sparse "
                        "experts in every E layer")
    if problems:
        raise ValueError("arch 'nemotron_h' cannot run this model: "
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
            f"arch 'nemotron_h' needs LlamaConfig fields {missing}, which "
            "this program's ray_tpu/models/llama.py does not have: it cannot "
            "run this stack (layers of ONE part each, a Mamba-2 mixer with "
            "groups of B and C, ungated relu^2 experts under a sigmoid "
            "router)")
    held = model["n_routed_experts"]
    ep = model.get("expert_parallel")
    # d_ff: one routed expert's width.
    return LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["moe_intermediate_size"],
        norm_eps=model["layer_norm_epsilon"], max_seq=int(max_seq),
        param_dtype=jnp.dtype(dtypes["params"]),
        dtype=jnp.dtype(dtypes["activations"]),
        layer_parts=model["hybrid_override_pattern"],
        ssm_state=model["ssm_state_size"], ssm_heads=model["mamba_num_heads"],
        ssm_head_dim=model["mamba_head_dim"], ssm_groups=model["n_groups"],
        ssm_conv=model["conv_kernel"], rope=False, tie_embeddings=False,
        n_experts=ep["routed_experts_total"] if ep else held,
        top_k_experts=model["num_experts_per_tok"],
        norm_topk_prob=bool(model.get("norm_topk_prob", True)),
        moe_aux_weight=0.0,
        experts_held=(ep["rank"] * held, held) if ep else None,
        n_shared_experts=model["moe_shared_expert_intermediate_size"]
        // model["moe_intermediate_size"],
        router_score="sigmoid", n_group=model.get("n_group", 1),
        topk_group=model.get("topk_group", 1),
        routed_scale=float(model["routed_scaling_factor"]),
        ffn="relu2")


def init_params(cfg, seed: int):
    """Weights on the device from the seed, as every adapter's, the routed
    experts' W_down times ROUTED_DOWN (in place), and then the routers'
    selection bias (`e_score_correction_bias`) BALANCED by the rule
    that trains it, as `benchmark/models/mimo.py::init_params` balances its
    own and says why (the step count and the choice function are dots',
    imported): a share's timing follows its routing, and a bias left as drawn
    makes this chip's share of the assignments the seed's accident. Each E
    layer's bias, in the order the layers run, is moved until every expert
    meets as many as any other of the assignments of BALANCE_SEQUENCES
    sequences of SEQUENCE_TOKENS seeded ids each (MANY sequences, not one
    long one: a sequence has its own popular experts), taken through this
    block's reference layers with the bias already found for the layers
    before. The matmuls run at the device's default precision: what is
    balanced is a distribution, not a value."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_nemotron_h as ref
    from benchmark.models import llama as dense
    from benchmark.models.dots import BALANCE_STEPS
    from benchmark.reference_dots import combine_from_scores
    params = dense.init_params(cfg, seed)
    if "experts" not in params:
        return params
    shrink = jax.jit(lambda w: (w.astype(jnp.float32) * ROUTED_DOWN).astype(
        w.dtype), donate_argnums=0)
    params["experts"]["w_down"] = shrink(params["experts"]["w_down"])
    m = _model_of(cfg)
    k, eps, total = cfg.top_k_experts, cfg.norm_eps, cfg.n_experts
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

    layer = jax.jit(lambda x, lp, part, i: ref._layer(x, lp, m, part, layer=i),
                    static_argnames="part")
    scores = jax.jit(lambda x, w, router: jax.nn.sigmoid(
        ref._rms_norm(x, w, eps) @ router.astype(jnp.float32)))
    xs = [params["embed"][row].astype(jnp.float32) for row in ids]
    for name, i, part in ref.stack_order(m):
        stack = params[name]
        lp = {n: v if n in ref._EXPERTS else v[i].astype(jnp.float32)
              for n, v in stack.items()}
        if part == "E":
            bias = balance(jnp.concatenate(
                [scores(x, lp["mlp_norm"], lp["router"]) for x in xs]))
            lp["router_bias"] = bias
            stack["router_bias"] = stack["router_bias"].at[i].set(
                bias.astype(stack["router_bias"].dtype))
        xs = [layer(x, lp, part=part, i=i) for x in xs]
    return params


def _model_of(cfg):
    """The published keys `reference_nemotron_h` reads, back from the
    program's config (`build_config` undone)."""
    offset, held = cfg.experts_held or (0, cfg.n_experts)
    out = {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "hybrid_override_pattern": cfg.layer_parts,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "layer_norm_epsilon": cfg.norm_eps,
        "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
        "ssm_state_size": cfg.ssm_state, "n_groups": cfg.ssm_groups,
        "conv_kernel": cfg.ssm_conv,
        "num_experts_per_tok": cfg.top_k_experts,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scale, "n_routed_experts": held}
    if cfg.experts_held:
        out["expert_parallel"] = {"rank": offset // held,
                                  "routed_experts_total": cfg.n_experts}
    return out


def loss_fn(params, tokens, cfg, pctx):
    from ray_tpu.models import llama
    return llama.loss_fn(params, tokens, cfg, pctx)   # refuses, by name


def reference():
    from benchmark import reference_nemotron_h
    return reference_nemotron_h
