"""The two halves of the decoder block that do not depend on how the block
meets its KV cache, written once: training (`llama._layer_fwd`), the
engine's prefill and its decode step (`serve/engine.py`) all call them.

  attention_inputs   attn_norm -> q, k, v -> (q/k norm) -> heads -> RoPE
                     (+ a sparse-attention indexer's queries, key and head
                     weights, from the same normed input)
  feed_forward       mlp_norm -> dense SwiGLU, or router + experts
  mamba_mixer        a state-space layer's whole mixer, over a sequence or
                     for one token a slot: its state is an argument and a
                     result, so where the state lives is the caller's

and the two ways a program that runs no gradient (serving) holds its layer
stacks differently from training, each so that the compiler reads a layer's
weights where they lie: `fuse_qkv` / `split_qkv` and `expert_stacks`.

Between them sits the attention itself, which stays with its caller: flash
or ring attention over the whole sequence, or a scatter into and a gather out
of the paged arena.

`x` is `[batch, seq, d_model]` (training, prefill) or `[slots, d_model]` (a
decode step: one token a slot). Scope names are the one vocabulary a device
trace is reduced by (benchmark/program_trace.py); the sparse half's own
(`router`, `moe_dispatch`, `experts`, `moe_combine`) lie inside `mlp`, and
`qk_norm` inside `qkv`, so the outer names keep their meaning.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.moe import moe_ffn
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.ssm import causal_conv, selective_scan, ssm_step


def attention_inputs(lp: Dict[str, jax.Array], x: jax.Array, cfg,
                     rope: Callable[[jax.Array], jax.Array],
                     index_rope: Optional[Callable] = None) -> Tuple:
    """-> q `[batch, heads, seq, hd]`, k and v `[batch, kv_heads, seq, hd]`
    (`[slots, heads, hd]` for a decode step), q and k rotated by `rope`,
    which is handed a tensor in that layout. `cfg.qk_norm` True: q and k are
    RMS-normalised over their WHOLE projection, all heads together, before
    the split into heads (OLMoE); "head": over each head's own `hd`, after
    it (the Qwen3 family). `lp` holds the projection as training does,
    `wq`, `wk`, `wv`, or as serving does, one `wqkv` (`fuse_qkv`).

    With `cfg.index_topk` (a learned sparse-attention indexer,
    `ops/sparse_attention.py`) a fourth element follows: (qI `[batch,
    index_heads, seq, Id]`, kI `[batch, 1, seq, Id]`, w `[batch, seq,
    index_heads]` float32; `[slots, ...]` without the seq axis for a decode
    step), projected from the same normed input (three more matrices, or
    three more column groups of `wqkv`), kI through a LayerNorm, qI and kI
    rotated by `index_rope`, w scaled by index_heads^-1/2 * Id^-1/2."""
    lead = x.shape[:-1]
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype

    def heads(t, n, d=hd):
        t = t.reshape(*lead, n, d)
        return t.transpose(0, 2, 1, 3) if len(lead) == 2 else t

    with jax.named_scope("attn_norm"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("qkv"):
        if "wqkv" in lp:    # serving (`fuse_qkv`): one matmul, its columns
            parts = jnp.split(h @ lp["wqkv"].astype(dt), _qkv_ends(cfg),
                              axis=-1)
        else:               # training: a matrix each
            parts = [h @ lp[name].astype(dt) for name in _fused_names(cfg)]
        q, k, v = parts[:3]
        if cfg.qk_norm is True:
            with jax.named_scope("qk_norm"):
                q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
                k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q, k, v = heads(q, H), heads(k, KVH), heads(v, KVH)
        if cfg.qk_norm == "head":
            with jax.named_scope("qk_norm"):
                q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
                k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        if cfg.index_topk:
            IH, Id = cfg.index_heads, cfg.index_head_dim
            iq, ik, iw = parts[3:]
            ik = layer_norm(ik, lp["ik_norm"], lp["ik_bias"], cfg.norm_eps)
            qi, ki = heads(iq, IH, Id), heads(ik, 1, Id)
            w = iw.astype(jnp.float32) * (IH ** -0.5 * Id ** -0.5)
    with jax.named_scope("rope"):
        q = rope(q)
        k = rope(k)
        if cfg.index_topk:
            return q, k, v, (index_rope(qi), index_rope(ki), w)
    return q, k, v


def feed_forward(lp: Dict[str, jax.Array], x: jax.Array, cfg,
                 live: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """x + FFN(mlp_norm(x)). A dense block returns (x, None); a sparse one
    (x, (aux loss, tokens per expert `[n_experts]` int32)), the count over the
    rows `live` marks (`x`'s shape less its last axis; every row if None).
    With `layer`, the experts' weights in `lp` are the stacks of all layers
    and `layer` this block's index (`expert_stacks`, `ops.moe.moe_ffn`)."""
    dt = cfg.dtype
    with jax.named_scope("mlp_norm"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        if cfg.n_experts > 0:
            out, aux, counts = moe_ffn(
                h.reshape(-1, h.shape[-1]), lp["router"].astype(dt),
                lp["w_up"].astype(dt), lp["w_gate"].astype(dt),
                lp["w_down"].astype(dt), top_k=cfg.top_k_experts,
                norm_topk_prob=cfg.norm_topk_prob,
                live=None if live is None else live.reshape(-1), layer=layer)
            return x + out.reshape(x.shape), (aux, counts)
        gate = h @ lp["w_gate"].astype(dt)
        up = h @ lp["w_up"].astype(dt)
        return x + (jax.nn.silu(gate) * up) @ lp["w_down"].astype(dt), None


def mamba_mixer(lp: Dict[str, jax.Array], x: jax.Array, cfg, state=None,
                window=None, *, step: bool = False, length=None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x + mixer(norm(x)) for a state-space (Mamba-1) layer, Jamba's: the
    time step, B and C each RMS-normalised after their projection.

      u, z  = split(norm(x) W_in)                               scope ssm_in
      u     = silu(b + sum_k w[k] * u_{t-K+1+k})                      conv
      r, B, C = split(u W_x), each RMS-normalised;
      dt    = softplus(r W_dt + b_dt) (float32), A = -exp(A_log)
                                                                ssm_params
      s_t   = exp(dt_t (x) A) s_{t-1} + (dt_t u_t) (x) B_t;
      y_t   = s_t . C_t + D u_t                                      scan
      out   = x + (y * silu(z)) W_out                             ssm_out

    x `[S, D]`, one sequence, from `state` `[N, Di]` and `window` `[K - 1,
    Di]` (None: a sequence's start), through `ops.ssm.selective_scan`, which
    also applies the gate; rows at and past `length` leave state and window
    as they were. Or, with `step`, x `[ns, D]`, one token a slot, from
    `state` `[ns, N, Di]` and `window` `[K - 1, ns, Di]` (`ops/slot_state.py`
    has both layouts). -> (out, state, window)."""
    dt = cfg.dtype
    R, N, eps = cfg.ssm_dt_rank, cfg.ssm_state, cfg.norm_eps
    with jax.named_scope("ssm_in"):
        h = rms_norm(x, lp["norm"], eps)
        u, z = jnp.split(h @ lp["in_proj"].astype(dt), 2, axis=-1)
    with jax.named_scope("conv"):
        if step:    # each slot a sequence of one row, its window its own
            u, window = jax.vmap(causal_conv, (0, None, None, 1), (0, 1))(
                u[:, None], lp["conv_w"], lp["conv_b"], window)
            u = u[:, 0]
        else:
            u, window = causal_conv(u, lp["conv_w"], lp["conv_b"], window,
                                    length)
        u = jax.nn.silu(u).astype(dt)
    with jax.named_scope("ssm_params"):
        r, b, c = jnp.split(u @ lp["x_proj"].astype(dt), [R, R + N], axis=-1)
        r = rms_norm(r, lp["dt_norm"], eps)
        b = rms_norm(b, lp["b_norm"], eps)
        c = rms_norm(c, lp["c_norm"], eps)
        step_size = jax.nn.softplus(
            jnp.dot(r, lp["dt_proj"].astype(dt),
                    preferred_element_type=jnp.float32)
            + lp["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(lp["A_log"].astype(jnp.float32))
    with jax.named_scope("scan"):
        if step:
            y, state = ssm_step(u, step_size, a, b, c, lp["D"], state)
        else:
            y, state = selective_scan(u, step_size, a, b, c, lp["D"], state,
                                      length, z=z)
    with jax.named_scope("ssm_out"):
        if step:
            y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
        return x + y @ lp["out_proj"].astype(dt), state, window


_QKV = ("wq", "wk", "wv")
_INDEX = ("wiq", "wik", "wiw")


def _fused_names(cfg) -> Tuple[str, ...]:
    """The projections of the block's normed input, in the order of the
    fused stack's column groups: q, k, v, then an indexer's three."""
    return _QKV + (_INDEX if cfg.index_topk else ())


def _qkv_ends(cfg) -> List[int]:
    """The columns of the fused projection at which each group but the last
    ends."""
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ends = [H * hd, (H + KVH) * hd]
    if cfg.index_topk:
        iq = (H + 2 * KVH) * hd
        ends += [iq, iq + cfg.index_heads * cfg.index_head_dim,
                 iq + (cfg.index_heads + 1) * cfg.index_head_dim]
    return ends


def fuse_qkv(params: Dict[str, Any]) -> Dict[str, Any]:
    """A model's parameters as the serving programs take them: the layers'
    `wq`, `wk`, `wv` `[L, d_model, n * hd]` (and an indexer's `wiq`, `wik`,
    `wiw`, further columns of the same input) joined along their columns into
    ONE stack `wqkv` `[L, d_model, (H + 2 KVH) * hd (+ ...)]`, once, when a
    server is built. Handed three stacks, the TPU compiler lays each out for
    its own matmul, copies all three at every program's entry, slices a
    layer's matrices out as copies, and keeps the whole `wk` stack moving in
    and out of fast memory every layer of every decode step (a quarter of a
    Mistral decode step; PERF.md, PR 30). One stack it reads a layer at a
    time, in place, inside the matmul, as it reads the MLP's. The columns are
    the separate matmuls' columns, so q, k and v are what they were, to the
    order in which a row is summed. Training keeps a matrix each
    (`attention_inputs`): its gradient, optimizer state, checkpoints and `tp`
    sharding are by matrix. The result holds no reference to the stacks it
    joined."""
    layers = dict(params["layers"])
    names = _QKV + tuple(n for n in _INDEX if n in layers)
    layers["wqkv"] = jnp.concatenate([layers.pop(k) for k in names], axis=-1)
    return dict(params, layers=layers)


def split_qkv(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """`fuse_qkv` undone: the tree a checkpoint, the train step or a plain
    reference reads, its matrices cut from the fused stack anew at every
    call (bit for bit what was fused), so nothing holds a second copy of the
    projections longer than its caller does."""
    layers = dict(params["layers"])
    parts = jnp.split(layers.pop("wqkv"), _qkv_ends(cfg), axis=-1)
    return dict(params, layers=dict(layers, **dict(zip(_fused_names(cfg),
                                                       parts))))


def expert_stacks(layers: Dict[str, jax.Array], cfg
                  ) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """A model's stacked layer parameters in the serving layout (`fuse_qkv`;
    the published one is refused: serving has one projection path), split
    for a scan over the layers that runs no gradient: (what the scan slices a
    layer at a time, the experts' stacks its body reads whole and hands
    `feed_forward` with the layer's index; empty for a dense model). The
    stacks come back in the compute dtype, cast here, once and outside the
    scan: nothing where they are stored in it (the `Engine` sees to that when
    it is built), a copy of all the experts a call where they are not. Training keeps the slice
    (`llama._layer_fwd`): a gradient through the stack would be written whole
    once a layer."""
    if "wqkv" not in layers:
        raise ValueError("a serving program takes `fuse_qkv(params)`: one "
                         "q/k/v projection stack, not a matrix each")
    names = ("w_gate", "w_up", "w_down") if cfg.n_experts > 0 else ()
    return ({k: v for k, v in layers.items() if k not in names},
            {k: layers[k].astype(cfg.dtype) for k in names})


def expert_stats(counts: jax.Array) -> jax.Array:
    """What a serving program hands back of one layer's routing, `[E + 1]`
    int32 that add up over layers and steps: tokens per expert, then the
    number of distinct experts touched."""
    return jnp.concatenate(
        [counts, jnp.sum(counts > 0, dtype=jnp.int32)[None]])
