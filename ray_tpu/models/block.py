"""The two halves of the decoder block that do not depend on how the block
meets its KV cache, written once: training (`llama._layer_fwd`), the
engine's prefill and its decode step (`serve/engine.py`) all call them.

  attention_inputs   attn_norm -> q, k, v -> (q/k norm) -> heads -> RoPE
                     (+ a sparse-attention indexer's queries, key and head
                     weights, from the same normed input)
  latent_attention_inputs
                     its peer for latent attention (MLA): attn_norm -> q
                     through its latent and norm, one latent row and one
                     rotated key a token, and either every head's key and
                     value up-projected from the latent (a prompt) or the key
                     up-projection absorbed into q (a decode step, whose
                     attention then reads the cached rows themselves;
                     `latent_attention_output` is the value's half)
  mixed_attention_inputs
                     its peer for a stack of window and full attention layers
                     (`cfg.attn_pattern`): attn_norm -> q, k, v at the widths
                     of the layer's KIND (its query heads too), each q and k
                     head in two parts, the one RoPE turns (at the kind's
                     frequencies) and the one it passes (none where the kind
                     turns the whole head), and a gate's logit a query head
  feed_forward       mlp_norm -> dense SwiGLU, or router + experts (+ a
                     shared expert; the experts a share of the router's)
  mamba_mixer        a state-space layer's whole mixer, over a sequence or
                     for one token a slot: its state is an argument and a
                     result, so where the state lives is the caller's
  mamba2_mixer       its peer for Mamba-2 (`cfg.ssm_heads`; the Granite 4.0-H
                     family): heads of channels with one scalar decay each,
                     a prompt by the chunked dual form
  conv_mixer         a gated short-convolution layer's whole operator (the
                     LFM2 family), likewise once for a sequence and for one
                     token a slot, its window an argument and a result
  retention_mixer    a power-retention layer's whole mixer (the Brumby
                     family): the block's own q, k, v, q/k norm and RoPE, a
                     gate a kv head, `ops/retention.py`, likewise once for a
                     prompt and for one token a slot

  block_sparse_attention_inputs
                     the cache-free half of a layer that selects BLOCKS (the
                     MiniCPM-SALA family's `minicpm4` kind): attn_norm -> q,
                     k, v and the output gate's logits, NO rotation
  linear_mixer       a decayed linear-attention layer's whole mixer (the same
                     family's `lightning-attn` kind): q, k, v, a norm a head,
                     RoPE, `ops/linear_attention.py`, the output's norm and
                     gate, likewise once for a prompt and for one token a slot

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

import itertools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import linear_attention, retention
from ray_tpu.ops.moe import moe_ffn
from ray_tpu.ops.norms import layer_norm, rms_norm
# The module, not its name: tests put an interpreted `step_layer` in its place.
from ray_tpu.ops import slot_state
from ray_tpu.ops.ssm import causal_conv, selective_scan, ssd_scan, ssm_step


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


def latent_attention_inputs(lp: Dict[str, jax.Array], x: jax.Array, cfg,
                            rope: Callable[[jax.Array], jax.Array], *,
                            absorb: bool = False) -> Tuple:
    """Latent attention's (MLA's) cache-free half, the DeepSeek-V3 block's:

      cq       = rmsnorm(h W_DQ);  [q_n ; q_r] = cq W_UQ  a head   scope q_latent
      [c ; kr] = h W_DKV;  c = rmsnorm(c)                          kv_latent
      q_r, kr rotated by `rope` (ONE kr a token, shared by all heads)   rope
      a prompt:       [k_n ; v] = c W_UKV  a head                   kv_up
      a decode step:  ql = q_n W_UK^T  a head  (`absorb`)           absorb

    `h` is the normed input; what a cache keeps of a token is `c` and the
    rotated `kr`, nothing else. -> for a prompt (x `[batch, seq, d_model]`)
    q_n `[b, H, s, dn]`, q_r `[b, H, s, dr]`, k_n `[b, H, s, dn]`, v `[b, H,
    s, dv]`, c `[b, s, rkv]`, kr `[b, s, dr]`: head h's key is `[k_n[h] ;
    kr]`. With `absorb` (x `[slots, d_model]`) ql `[ns, H, rkv]`, q_r `[ns, H,
    dr]`, c `[ns, rkv]`, kr `[ns, dr]`: head h's score against a cached row
    (c_s, kr_s) is `ql[h] . c_s + q_r[h] . kr_s`, the same number as `[q_n ;
    q_r] . [c_s W_UK ; kr_s]`, and its output `(sum_s p_s c_s) W_UV`
    (`latent_attention_output`). `lp` holds the up-projections as they are
    published, `w_uq` `[rq, H * (dn + dr)]` and `w_ukv` `[rkv, H * (dn +
    dv)]`, or as serving does, each cut by what it makes: `w_uq_n`, `w_uq_r`,
    `w_uk` `[rkv, H, dn]` and `w_uv` `[rkv, H, dv]` (`fuse_qkv`)."""
    lead = x.shape[:-1]
    H, rkv = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    dt, eps = cfg.dtype, cfg.norm_eps
    seq = len(lead) == 2

    with jax.named_scope("attn_norm"):
        h = rms_norm(x, lp["attn_norm"], eps)
    with jax.named_scope("qkv"):
        with jax.named_scope("q_latent"):
            cq = rms_norm(h @ lp["w_dq"].astype(dt), lp["q_norm"], eps)
            if "w_uq_n" in lp:      # serving: a matrix for each part of q
                q_n = (cq @ lp["w_uq_n"].astype(dt)).reshape(*lead, H, dn)
                q_r = (cq @ lp["w_uq_r"].astype(dt)).reshape(*lead, H, dr)
            else:
                q = (cq @ lp["w_uq"].astype(dt)).reshape(*lead, H, dn + dr)
                q_n, q_r = q[..., :dn], q[..., dn:]
            if seq:
                q_n, q_r = (t.transpose(0, 2, 1, 3) for t in (q_n, q_r))
        with jax.named_scope("kv_latent"):
            ckr = h @ lp["w_dkv"].astype(dt)
            c = rms_norm(ckr[..., :rkv], lp["kv_norm"], eps)
            kr = ckr[..., rkv:]
    with jax.named_scope("rope"):
        q_r = rope(q_r)
        kr = rope(kr[:, None])[:, 0]        # one head for the rotation
    w_uk, w_uv = _up_projections(lp, cfg)
    with jax.named_scope("qkv"):
        if absorb:
            with jax.named_scope("absorb"):
                ql = jnp.einsum("nhd,rhd->nhr", q_n, w_uk.astype(dt))
            return ql, q_r, c, kr
        with jax.named_scope("kv_up"):
            k_n = jnp.einsum("bsr,rhd->bhsd", c, w_uk.astype(dt))
            v = jnp.einsum("bsr,rhd->bhsd", c, w_uv.astype(dt))
    return q_n, q_r, k_n, v, c, kr


def latent_attention_output(lp: Dict[str, jax.Array], ol: jax.Array, cfg
                            ) -> jax.Array:
    """The value's half of the absorbed form: ol `[ns, H, rkv]`, each head's
    softmax-weighted sum of the cached latent rows, through that head's value
    up-projection -> `[ns, H * dv]`, what `wo` takes."""
    _, w_uv = _up_projections(lp, cfg)
    with jax.named_scope("absorb"):
        o = jnp.einsum("nhr,rhd->nhd", ol.astype(cfg.dtype),
                       w_uv.astype(cfg.dtype))
    return o.reshape(ol.shape[0], -1)


def _up_projections(lp, cfg):
    """(W_UK `[rkv, H, dn]`, W_UV `[rkv, H, dv]`) from either layout."""
    if "w_uk" in lp:
        return lp["w_uk"], lp["w_uv"]
    w = lp["w_ukv"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def mixed_attention_inputs(lp: Dict[str, jax.Array], x: jax.Array, cfg,
                           stack: str,
                           rope: Callable[[jax.Array], jax.Array]) -> Tuple:
    """The cache-free half of a layer of a mixed-attention stack
    (`cfg.attn_pattern`), `stack` the layer's kind (`window`, or `dense` /
    `layers`: full attention), which decides BY KIND its query heads, its kv
    heads and how much of a head RoPE turns (`cfg.attention_kind`); the
    caller's `rope` rotates at that kind's frequencies.

    A q or k head is `cfg.head_dim` wide, of which RoPE turns the kind's
    first `rotary_dim` (r) and passes the rest (n); a v head is
    `cfg.v_head_dim`. -> (q_n, q_r, k_n, k_r, v, gate): `[batch, heads, seq,
    d]` for a prompt, `[slots, heads, d]` for a decode step, q_r and k_r
    rotated, v times `cfg.value_scale`; q_n and k_n None where the kind
    turns the whole head; gate None without `cfg.attn_gate`, else the gate's
    logits a query head, `[batch, seq, heads]` or `[slots, heads]`, from the
    same normed input.
    The two parts apart, because 192 = 128 + 64 is a tile and a half: the
    prefill kernels take such a key in two parts (`ops.attention.
    mixed_flash_attention`, which joins the parts of a 128-wide head
    itself), and the caches hold `[k_n ; k_r]`, the passed
    part on the tile's boundary. `lp` holds the projections as published,
    `wq`, `wk`, `wv` (a head `[r ; n]`) and `wg`, or as serving does, one
    `wqkv` whose column groups are the results (`fuse_qkv`), so that nothing
    is cut inside a program and the gate costs no second pass over the
    input."""
    lead = x.shape[:-1]
    KVH, _, _, _, H, dr = cfg.attention_kind(stack)
    dt = cfg.dtype

    def heads(t, n):
        t = t.reshape(*lead, n, -1)
        return t.transpose(0, 2, 1, 3) if len(lead) == 2 else t

    with jax.named_scope("attn_norm"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("qkv"):
        if "wqkv" in lp:
            parts = dict(zip(_mixed_widths(cfg, stack), jnp.split(
                h @ lp["wqkv"].astype(dt), _mixed_ends(cfg, stack),
                axis=-1)))
            q_n, q_r, k_n, k_r = (
                heads(parts[k], n) if k in parts else None
                for k, n in (("q_n", H), ("q_r", H), ("k_n", KVH),
                             ("k_r", KVH)))
            # (the gate's group is padded to whole tiles: `_mixed_widths`)
            v, gate = parts["v"], parts["g"][..., :H] if "g" in parts \
                else None
        else:
            q = heads(h @ lp["wq"].astype(dt), H)
            k = heads(h @ lp["wk"].astype(dt), KVH)
            v = h @ lp["wv"].astype(dt)
            gate = h @ lp["wg"].astype(dt) if cfg.attn_gate else None
            q_r, q_n, k_r, k_n = (q[..., :dr], q[..., dr:], k[..., :dr],
                                  k[..., dr:]) if dr < cfg.head_dim \
                else (q, None, k, None)
        # The values are scaled, which is the attention's output scaled, at
        # KVH heads a position and not H: what a cache keeps is this v.
        v = heads(v, KVH) * jnp.asarray(cfg.value_scale, v.dtype)
    with jax.named_scope("rope"):
        q_r, k_r = rope(q_r), rope(k_r)
    return q_n, q_r, k_n, k_r, v, gate


def gated(attn: jax.Array, gate: Optional[jax.Array]) -> jax.Array:
    """A mixed-attention layer's output `[.., H, dv]` times sigmoid of its
    gate's logits `[.., H]`, a head (`cfg.attn_gate`), in float32, back in
    `attn`'s dtype; `gate` None: `attn` as it is."""
    if gate is None:
        return attn
    with jax.named_scope("attn_gate"):
        return (attn.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
                ).astype(attn.dtype)


def _mixed_widths(cfg, stack: str) -> Dict[str, int]:
    """The column groups of a mixed-attention layer's fused projection, in
    their order, each with its width, the empty ones left out: every head's
    passed part of q, of k, v, every head's rotary part of q, of k, and the
    gate's logit a query head, in whole tiles of 128 columns (zeros after the
    heads'): a stack whose rows are no whole tiles is laid out anew at every
    program's entry (0.42 GB a decode chunk at Laguna's 72 heads, compiled
    for a v5e, PR 62)."""
    KVH, _, _, _, H, dr = cfg.attention_kind(stack)
    dn = cfg.head_dim - dr
    widths = {"q_n": H * dn, "k_n": KVH * dn, "v": KVH * cfg.v_head_dim,
              "q_r": H * dr, "k_r": KVH * dr,
              "g": -(-H // 128) * 128 if cfg.attn_gate else 0}
    return {k: w for k, w in widths.items() if w}


def _mixed_ends(cfg, stack: str) -> List[int]:
    """Where each column group of a mixed-attention layer's fused projection
    but the last ends."""
    return list(itertools.accumulate(_mixed_widths(cfg, stack).values()))[:-1]


def feed_forward(lp: Dict[str, jax.Array], x: jax.Array, cfg,
                 live: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """x + FFN(mlp_norm(x)). A dense block returns (x, None); a sparse one
    (x, (aux loss, tokens per expert `[n_experts]` int32)), the count over the
    rows `live` marks (`x`'s shape less its last axis; every row if None).
    With `layer`, the experts' weights in `lp` are the stacks of all layers
    and `layer` this block's index (`expert_stacks`, `ops.moe.moe_ffn`).
    The branch is multiplied by `cfg.residual_scale` before the add.
    A layer is sparse if it has a router: a sparse model's leading dense
    layers (`cfg.first_dense`) have none. The router's variant, the share of
    the experts held (the count is then per HELD expert), a shared expert and
    the expert's form (gated or not, its activation) are `cfg`'s
    (`LlamaConfig.routing`, `experts_held`, `n_shared_experts`, `ffn`,
    `up_out_in`); an ungated expert has no `w_gate` in `lp`."""
    dt = cfg.dtype
    with jax.named_scope("mlp_norm"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        if cfg.n_experts > 0 and "router" in lp:
            # (None, None, None for a uniform stack: `__post_init__`.)
            routing = cfg.routing()
            if routing is not None and "router_bias" in lp:
                routing = dict(routing, bias=lp["router_bias"])
            def leaf(name):     # None: an ungated expert's gate
                return lp[name].astype(dt) if name in lp else None

            more = dict(
                routing=routing, held=cfg.experts_held, act=cfg.ffn_act,
                out_in=cfg.up_out_in,
                shared=tuple(leaf("ws_" + k) for k in ("gate", "up", "down"))
                if cfg.n_shared_experts else None)
            out, aux, counts = moe_ffn(
                h.reshape(-1, h.shape[-1]), lp["router"].astype(dt),
                leaf("w_up"), leaf("w_gate"), leaf("w_down"),
                top_k=cfg.top_k_experts,
                norm_topk_prob=cfg.norm_topk_prob,
                live=None if live is None else live.reshape(-1), layer=layer,
                **more)
            return x + scaled(out.reshape(x.shape), cfg), (aux, counts)
        gate = h @ lp["w_gate"].astype(dt)
        up = h @ lp["w_up"].astype(dt)
        return x + scaled((jax.nn.silu(gate) * up) @ lp["w_down"].astype(dt),
                           cfg), None


def scaled(branch: jax.Array, cfg) -> jax.Array:
    """A residual branch times `cfg.residual_scale` (the Granite family's
    `residual_multiplier`), in the branch's dtype; 1.0 emits nothing."""
    if cfg.residual_scale == 1.0:
        return branch
    return branch * jnp.asarray(cfg.residual_scale, branch.dtype)


def _slot_conv(lp, rows, window):
    """A decode step's convolution: each slot a sequence of ONE row (`rows`
    `[ns, C]`), its window (`[K - 1, ns, C]`) its own -> (the rows, float32;
    the windows moved on a row)."""
    rows, window = jax.vmap(causal_conv, (0, None, None, 1), (0, 1))(
        rows[:, None], lp["conv_w"], lp.get("conv_b"), window)
    return rows[:, 0], window


def _ride(rows, rode, active):
    """`rows` `[S, ...]` of a prompt's bucket with the riding slots' `rode`
    `[ns, ...]` in its LAST ns rows: slot i's in row S - ns + i where `active`
    marks it; any other row stays the prompt's (or its padding). An update
    of ns rows IN PLACE, so `rows` is what a kernel or a matmul reads next
    and is written out anyway (the convolution's output after its
    activation, in the compute dtype; a scan's output): rows that a fusion
    would have kept to itself are first written out whole (the
    convolution's float32 sums: 84 MB a layer of a 4,096-row bucket at
    Jamba's widths; compiled for a v5e, PR 58), and a select over all the
    rows costs every layer a pass (my chip runs, PR 58: §6)."""
    ns = active.shape[0]
    keep = active.reshape((ns,) + (1,) * (rode.ndim - 1))
    return rows.at[-ns:].set(
        jnp.where(keep, rode.astype(rows.dtype), rows[-ns:]))


def mamba_mixer(lp: Dict[str, jax.Array], x: jax.Array, cfg, state=None,
                window=None, *, step: bool = False, length=None, riders=None,
                layer=None, active=None) -> Tuple[jax.Array, ...]:
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
    has both layouts). -> (out, state, window).

    RIDERS: a sequence whose LAST ns rows (past `length`) are one token a
    slot: `riders` the slots' whole state (`ops/slot_state.py`'s pair), of
    which layer `layer`'s rows of the slots `active` `[ns]` marks are read
    and written. Every row-wise stage runs once over all the rows;
    between them a riding slot's row takes the step's path (`_slot_conv`
    against its own window, `ssm_step` from its own state, the gate) and the
    sequence's rows the sequence's, which are told `length`, so neither
    reaches the other: the sequence's rows, state and window are what they
    are without riders, a riding slot's row and its state what a step gives
    it, and an idle slot's stay. -> (out, state, window, the slots' state)."""
    dt = cfg.dtype
    R, N, eps = cfg.ssm_dt_rank, cfg.ssm_state, cfg.norm_eps
    if riders is not None:
        ns = active.shape[0]
        # As in a decode step, the state's read and its write back are the
        # update's traffic, under the scope that times it.
        with jax.named_scope("scan"):
            slot_ssm, slot_window = slot_state.layer_state(riders, layer)
    with jax.named_scope("ssm_in"):
        h = rms_norm(x, lp["norm"], eps)
        u, z = jnp.split(h @ lp["in_proj"].astype(dt), 2, axis=-1)
    with jax.named_scope("conv"):
        if step:
            u, window = _slot_conv(lp, u, window)
        else:
            rows = u
            u, window = causal_conv(rows, lp["conv_w"], lp["conv_b"], window,
                                    length)
        u = jax.nn.silu(u).astype(dt)
        if riders is not None:
            rode, slot_window = _slot_conv(lp, rows[-ns:], slot_window)
            u = _ride(u, jax.nn.silu(rode), active)
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

    def gated(y, z):    # a step's gate; a sequence's is the scan's own
        return (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt)

    with jax.named_scope("scan"):
        if step:
            y, state = ssm_step(u, step_size, a, b, c, lp["D"], state)
        else:
            y, state = selective_scan(u, step_size, a, b, c, lp["D"], state,
                                      length, z=z)
            if riders is not None:
                rode, slot_ssm = ssm_step(u[-ns:], step_size[-ns:], a,
                                          b[-ns:], c[-ns:], lp["D"], slot_ssm)
                y = _ride(y, gated(rode, z[-ns:]), active)
                riders = slot_state.update_layer(riders, layer, active,
                                                 slot_ssm, slot_window)
    with jax.named_scope("ssm_out"):
        if step:
            y = gated(y, z)
        out = x + scaled(y @ lp["out_proj"].astype(dt), cfg)
        return (out, state, window) + (() if riders is None else (riders,))


def mamba2_mixer(lp: Dict[str, jax.Array], x: jax.Array, cfg, state=None,
                 window=None, *, step: bool = False, length=None,
                 layer=None, active=None, riders=None
                 ) -> Tuple[jax.Array, ...]:
    """x + residual_scale * mixer(norm(x)) for a Mamba-2 layer
    (`cfg.ssm_heads` heads of `Di / H` channels, `cfg.ssm_groups` G groups of
    B and C, head h reading group `h // (H / G)`: one in the Granite 4.0-H
    family's, which is Bamba's; eight in the Nemotron-H family's), under
    `mamba_mixer`'s scopes:

      z, xBC, dt = split(norm(x) W_in, [Di, Di + 2GN, H]) (no bias)  ssm_in
      xBC   = silu(b + sum_k w[k] * xBC_{t-K+1+k})   (over x, B and C)  conv
      u, B, C = split(xBC, [Di, GN, GN]);
      dt = softplus(dt + b_dt) (float32, a head), A = -exp(A_log) (a head)
                                                                 ssm_params
      s_t   = exp(dt_t A) s_{t-1} + (dt_t u_t) (x) B_t;
      y_t   = s_t . C_t + D u_t     (a head's scalars, its group's B, C) scan
      out   = x + residual_scale * rmsnorm(y * silu(z); w_norm) W_out
              (the gate BEFORE the norm, which is over each group's Di / G
              channels apart: all Di where there is one group)     ssm_out

    x `[S, D]`, one sequence, from `state` `[N, Di]` and `window` `[K - 1, Di
    + 2GN]` (None: a sequence's start), through `ops.ssm.ssd_scan`; rows at
    and past `length` leave state and window as they were. Or, with `step`,
    x `[ns, D]`, one token a slot, from `window` `[K - 1, ns, Di + 2GN]` and
    `state` the slots' WHOLE state (`ops/slot_state.py`'s pair), of which
    layer `layer`'s rows of the slots `active` marks are read, updated and
    written where they lie, in one visit (`slot_state.step_layer`): the state
    handed back is the pair, and an idle slot's output row is not meaningful
    (and finite). -> (out, state, window).

    RIDERS, as `mamba_mixer`'s: `riders` the slots' whole state with `layer`
    and `active`, the sequence's last ns rows one token a slot; a riding
    slot's row goes through `_slot_conv` against its own window and
    `slot_state.step_layer` on its own state where it lies, the gate and the
    norm are every row's. -> (out, state, window, the slots' state)."""
    dt = cfg.dtype
    Di, N, eps = cfg.ssm_inner, cfg.ssm_state, cfg.norm_eps
    G = cfg.ssm_groups
    if riders is not None:
        ns = active.shape[0]
        with jax.named_scope("scan"):   # the window's read: a decode step's
            _, slot_window = slot_state.layer_state(riders, layer)
    with jax.named_scope("ssm_in"):
        h = rms_norm(x, lp["norm"], eps)
        z, xbc, r = jnp.split(h @ lp["in_proj"].astype(dt),
                              [Di, Di + cfg.ssm_conv_channels], axis=-1)
    with jax.named_scope("conv"):
        if step:
            xbc, window = _slot_conv(lp, xbc, window)
        else:
            rows = xbc
            xbc, window = causal_conv(rows, lp["conv_w"], lp["conv_b"],
                                      window, length)
        xbc = jax.nn.silu(xbc).astype(dt)
        if riders is not None:
            rode, slot_window = _slot_conv(lp, rows[-ns:], slot_window)
            xbc = _ride(xbc, jax.nn.silu(rode), active)
    with jax.named_scope("ssm_params"):
        u, b, c = jnp.split(xbc, [Di, Di + G * N], axis=-1)
        if G > 1:   # a group's B and C apart: [rows, G, N]
            b, c = (t.reshape(-1, G, N) for t in (b, c))
        step_size = jax.nn.softplus(r.astype(jnp.float32)
                                    + lp["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(lp["A_log"].astype(jnp.float32))
    with jax.named_scope("scan"):
        if step:
            y, state = slot_state.step_layer(state, layer, active, u,
                                             step_size, a, b, c, lp["D"])
        else:
            y, state = ssd_scan(u, step_size, a, b, c, lp["D"], state,
                                length)
            if riders is not None:
                rode, riders = slot_state.step_layer(
                    riders, layer, active, u[-ns:], step_size[-ns:], a,
                    b[-ns:], c[-ns:], lp["D"])
                y = _ride(y, rode, active)
                riders = slot_state.update_layer(riders, layer, active, None,
                                                 slot_window)
    with jax.named_scope("ssm_out"):
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        if G > 1:   # the norm over each group's channels apart
            y = rms_norm(gated.reshape(-1, G, Di // G),
                         lp["w_norm"].reshape(G, Di // G), eps).reshape(-1, Di)
        else:
            y = rms_norm(gated, lp["w_norm"], eps)
        y = y.astype(dt)
        out = x + scaled(y @ lp["out_proj"].astype(dt), cfg)
        return (out, state, window) + (() if riders is None else (riders,))


def conv_mixer(lp: Dict[str, jax.Array], x: jax.Array, cfg, window=None, *,
               step: bool = False, length=None, riders=None, layer=None,
               active=None) -> Tuple[jax.Array, ...]:
    """x + operator(norm(x)) for a gated short-convolution layer, the LFM2
    family's (`cfg.conv_layers`):

      B, C, X = split(norm(x) W_in);  z = B * X                scope conv_in
      c_t     = sum_j w[j] * z_{t-K+1+j}     (K = cfg.conv_taps, no bias,
                zeros before the sequence's start)                   conv
      out     = x + (C * c) W_out                               conv_out

    No position signal, and nothing kept of a sequence but the last K - 1
    rows of `z`. x `[S, D]`, one sequence, from `window` `[K - 1, D]` (None:
    a sequence's start); the window handed back is the K - 1 rows of z before
    row `length` (S if None), so a bucket's padding never enters it. Or, with
    `step`, x `[ns, D]`, one token a slot, from `window` `[K - 1, ns, D]`
    (`ops/slot_state.py`'s layout). The convolution is `ops.ssm.causal_conv`,
    Jamba's, without its bias. -> (out, window).

    RIDERS, as `mamba_mixer`'s: a sequence whose LAST ns rows (past `length`)
    are one token a slot, `riders` the slots' whole state (`ops/slot_state.py`'s
    pair, no recurrent part), of which layer `layer`'s window of the slots
    `active` `[ns]` marks is read and written. `conv_in` and `conv_out` run
    once over all the rows; between them a riding slot's row of z goes through
    `_slot_conv` against its own window, the step's path, and the sequence's
    rows through the sequence's, which is told `length`, so neither reaches
    the other. The riders' rows ride into the product `C * c` (what `W_out`
    reads, written out anyway), not into the convolution's float32 sums
    (`_ride`). -> (out, window, the slots' state)."""
    dt = cfg.dtype
    if riders is not None:
        ns = active.shape[0]
        # As in a decode step, the window's read and its write back are the
        # convolution's traffic, under the scope that times it.
        with jax.named_scope("conv"):
            _, slot_window = slot_state.layer_state(riders, layer)
    with jax.named_scope("conv_in"):
        h = rms_norm(x, lp["norm"], cfg.norm_eps)
        b, c, u = jnp.split(h @ lp["in_proj"].astype(dt), 3, axis=-1)
        z = b * u
    with jax.named_scope("conv"):
        if step:
            y, window = _slot_conv(lp, z, window)
        else:
            y, window = causal_conv(z, lp["conv_w"], None, window, length)
        if riders is not None:
            rode, slot_window = _slot_conv(lp, z[-ns:], slot_window)
            riders = slot_state.update_layer(riders, layer, active, None,
                                             slot_window)
    with jax.named_scope("conv_out"):
        y = c * y.astype(dt)
        if riders is not None:
            y = _ride(y, c[-ns:] * rode.astype(dt), active)
        out = x + y @ lp["out_proj"].astype(dt)
        return (out, window) + (() if riders is None else (riders,))


def retention_mixer(lp: Dict[str, jax.Array], x: jax.Array, cfg,
                    rope: Callable[[jax.Array], jax.Array], state=None, *,
                    step: bool = False, length=None, layer=None, active=None
                    ) -> Tuple[jax.Array, ...]:
    """x + retention(attn_norm(x)) W_o for a power-retention layer
    (`cfg.mixer` "retention", degree 2; `ops/retention.py` has the
    equations), under three scopes:

      q, k, v = `attention_inputs` (the block's projections, its q/k norm
                and RoPE, `rope` handed a tensor in its layout);
      g = logsigmoid(attn_norm(x) W_g + b_g)  float32, one a kv head  ret_in
      y = the operator: scores (q . k)^2 / d under the gates' running
          sums, normalised by their sum                              retention
      out = x + y W_o                                                  ret_out

    x `[1, S, D]`, one prompt, through `retention.retention_prompt` (rows at
    and past `length` write nothing to the state) -> (out, S, z), the state a
    slot keeps of the layer after row `length - 1`. Or, with `step`, x `[ns,
    D]`, one token a slot, and `state` the slots' WHOLE state pair
    (`ops/slot_state.py::empty_retention`), of which layer `layer`'s tiles of
    the slots `active` marks are read, updated and written where they lie in
    one visit (`slot_state.retention_step_layer`) -> (out, state); an idle
    slot's output row is not meaningful (and finite)."""
    dt = cfg.dtype
    with jax.named_scope("ret_in"):
        q, k, v = attention_inputs(lp, x, cfg, rope)
        # (the norm `attention_inputs` took, once more: one computation)
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        gamma = jax.nn.log_sigmoid(
            (h @ lp["wg"].astype(dt)).astype(jnp.float32)
            + lp["bg"].astype(jnp.float32))
    with jax.named_scope("retention"):
        if step:
            y, state = slot_state.retention_step_layer(
                state, layer, active, q, k, v, gamma)
            y = y.reshape(x.shape[0], -1)
        else:
            y, S, z = retention.retention_prompt(q[0], k[0], v[0], gamma[0],
                                                 length)
            y = y.transpose(1, 0, 2).reshape(1, x.shape[1], -1)
    with jax.named_scope("ret_out"):
        out = x + y.astype(dt) @ lp["wo"].astype(dt)
    return (out, state) if step else (out, S, z)


def _sala_widths(cfg, stack: str) -> Dict[str, int]:
    """The column groups of the fused projection of a layer of the stack
    `stack` (`sparse`, `linear`) of a model of linear and block-sparse
    layers, in their order, each with its width: q, k, v at the kind's own
    heads, then the output gate's logits, one a number of attention's
    output (left out where the kind has no gate)."""
    if stack == "sparse":
        q = cfg.n_heads * cfg.head_dim
        kv, gate = cfg.n_kv_heads * cfg.head_dim, cfg.sparse_gate
    else:
        q = kv = cfg.lightning_heads * cfg.lightning_head_dim
        gate = cfg.lightning_gate
    return {"wq": q, "wk": kv, "wv": kv, **({"wg": q} if gate else {})}


def _sala_parts(lp, h, cfg, stack: str) -> Dict[str, jax.Array]:
    """The normed input `h` through the layer's projections -> q, k, v
    (and the gate's logits) by the names of their matrices, from either
    layout: one `wqkv` (serving) or a matrix each (as published)."""
    dt = cfg.dtype
    widths = _sala_widths(cfg, stack)
    if "wqkv" in lp:
        ends = list(itertools.accumulate(widths.values()))[:-1]
        return dict(zip(widths, jnp.split(h @ lp["wqkv"].astype(dt), ends,
                                          axis=-1)))
    return {name: h @ lp[name].astype(dt) for name in widths}


def block_sparse_attention_inputs(lp: Dict[str, jax.Array], x: jax.Array,
                                  cfg) -> Tuple:
    """The cache-free half of a layer that selects blocks
    (`cfg.mixer_types` "minicpm4"): attn_norm -> q `[batch, heads, seq, hd]`,
    k and v `[batch, kv_heads, seq, hd]` (`[slots, heads, hd]` for a decode
    step), NO rotation and no norm of q or k, and the output gate's logits
    `[batch, seq, heads * hd]` (`[slots, heads * hd]`; None without
    `cfg.sparse_gate`), from the same normed input."""
    lead = x.shape[:-1]
    hd = cfg.head_dim

    def heads(t, n):
        t = t.reshape(*lead, n, hd)
        return t.transpose(0, 2, 1, 3) if len(lead) == 2 else t

    with jax.named_scope("attn_norm"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("qkv"):
        parts = _sala_parts(lp, h, cfg, "sparse")
        return (heads(parts["wq"], cfg.n_heads),
                heads(parts["wk"], cfg.n_kv_heads),
                heads(parts["wv"], cfg.n_kv_heads), parts.get("wg"))


def output_gated(attn: jax.Array, gate: Optional[jax.Array],
                 norm: Optional[jax.Array] = None, eps: float = 1e-6
                 ) -> jax.Array:
    """Attention's joined output `[.., heads * hd]`, RMS-normed over its
    whole width where `norm` (the weight) is given, times sigmoid of the
    gate's logits, one a number (None: no gate), in float32, back in the
    gate's dtype (`attn`'s without one): what `wo` takes of a linear or a
    block-sparse layer."""
    if gate is None and norm is None:
        return attn
    dt = attn.dtype if gate is None else gate.dtype
    with jax.named_scope("attn_gate"):
        y = attn.astype(jnp.float32)
        if norm is not None:
            y = rms_norm(y, norm, eps)
        if gate is not None:
            y = y * jax.nn.sigmoid(gate.astype(jnp.float32))
        return y.astype(dt)


def linear_mixer(lp: Dict[str, jax.Array], x: jax.Array, cfg,
                 rope: Callable[[jax.Array], jax.Array], rates: jax.Array,
                 state=None, *, step: bool = False, length=None, layer=None,
                 active=None) -> Tuple[jax.Array, jax.Array]:
    """x + residual_scale * mixer(attn_norm(x)) for a decayed
    linear-attention layer (`cfg.mixer_types` "lightning-attn";
    `ops/linear_attention.py` has the equations), under the block's scopes:

      q, k, v, g = attn_norm(x) W   (heads of `lightning_head_dim`, as many
                   kv heads as query heads); an RMS norm over each head of q
                   and of k (`lightning_qk_norm`)                       qkv
      q, k rotated by `rope`, handed a tensor in its layout
                   (`lightning_rope`; the whole head)                   rope
      o = the operator under the layer's `rates` [heads], q scaled by
          head width^-1/2                                  attn / linear_attn
      y = rmsnorm(o over the joined heads; o_norm) * sigmoid(g)
                   (`lightning_norm`, `lightning_gate`)       attn / attn_gate
      out = x + residual_scale * y W_o                              attn_out

    x `[1, S, D]`, one prompt, through `linear_attention.linear_prompt` (rows
    at and past `length` write nothing to the state) -> (out, S `[heads, d,
    d]` float32, the state a slot keeps of the layer after row `length - 1`).
    Or, with `step`, x `[ns, D]`, one token a slot, and `state` the slots'
    WHOLE state `[layers, ns, heads, d, d]`, of which layer `layer`'s tiles
    of the slots `active` marks are read, updated and written where they lie
    in one visit (`slot_state.linear_step_layer`) -> (out, state); an idle
    slot's output row is not meaningful (and finite)."""
    dt = cfg.dtype
    lead = x.shape[:-1]
    H, d = cfg.lightning_heads, cfg.lightning_head_dim
    scale = d ** -0.5

    def heads(t):
        t = t.reshape(*lead, H, d)
        return t.transpose(0, 2, 1, 3) if len(lead) == 2 else t

    with jax.named_scope("attn_norm"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("qkv"):
        parts = _sala_parts(lp, h, cfg, "linear")
        q, k, v = (heads(parts[n]) for n in _QKV)
        if cfg.lightning_qk_norm:
            with jax.named_scope("qk_norm"):
                q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
                k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if cfg.lightning_rope:
        with jax.named_scope("rope"):
            q, k = rope(q), rope(k)
    with jax.named_scope("attn"):
        with jax.named_scope("linear_attn"):
            if step:
                o, state = slot_state.linear_step_layer(
                    state, layer, active, q, k, v, rates, scale)
                o = o.reshape(lead[0], H * d)
            else:
                o, state = linear_attention.linear_prompt(
                    q[0], k[0], v[0], rates, scale, length)
                o = o.transpose(1, 0, 2).reshape(1, lead[1], H * d)
        y = output_gated(o, parts.get("wg"),
                         lp["o_norm"] if cfg.lightning_norm else None,
                         cfg.norm_eps).astype(dt)
    with jax.named_scope("attn_out"):
        return x + scaled(y @ lp["wo"].astype(dt), cfg), state


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


_LATENT_STACKS = ("layers", "dense")
_MIXED_STACKS = ("dense", "window", "layers")
_SALA_STACKS = ("sparse", "linear")


def _fuse_sala(params, cfg):
    """A model of linear and block-sparse layers as the serving programs
    take it: each stack's `wq`, `wk`, `wv` and the gate's `wg` joined into
    ONE `wqkv` (`_sala_widths`; every group whole tiles at the published
    widths), so that the gate costs no second pass over the input."""
    out = dict(params)
    for name in _SALA_STACKS:
        if name in params:
            layers = dict(params[name])
            layers["wqkv"] = jnp.concatenate(
                [layers.pop(k) for k in _sala_widths(cfg, name)], axis=-1)
            out[name] = layers
    return out


def _split_sala(params, cfg):
    out = dict(params)
    for name in _SALA_STACKS:
        if name in params:
            layers = dict(params[name])
            widths = _sala_widths(cfg, name)
            ends = list(itertools.accumulate(widths.values()))[:-1]
            parts = jnp.split(layers.pop("wqkv"), ends, axis=-1)
            out[name] = dict(layers, **dict(zip(widths, parts)))
    return out


def _fuse_mixed(params, cfg):
    """A mixed-attention model as the serving programs take it: each stack's
    `wq`, `wk`, `wv` (and the gate's `wg`) joined into ONE `wqkv` whose
    column groups are what `mixed_attention_inputs` hands on: every head's
    passed part of q, of k, v, then every head's rotary part of q and of k,
    then the gate's column a query head, at the KIND's heads and rotary
    width (`_mixed_widths`; at MiMo-V2's and at Laguna's published widths
    every group ends on a multiple of 128 columns, the gate's padded to
    one). A head's
    columns are cut at the kind's `rotary_dim` here, once; cut inside a
    program, at 64 of a head's 192 columns, it is a copy of q and of k every
    layer."""
    out = dict(params)
    for name in _MIXED_STACKS:
        if name not in params:
            continue
        layers = dict(params[name])
        KVH, _, _, _, H, dr = cfg.attention_kind(name)
        wq, wk, wv = (layers.pop(k) for k in _QKV)
        L, D, _ = wq.shape
        wq, wk = wq.reshape(L, D, H, -1), wk.reshape(L, D, KVH, -1)
        groups = {"q_n": wq[..., dr:].reshape(L, D, -1),
                  "k_n": wk[..., dr:].reshape(L, D, -1), "v": wv,
                  "q_r": wq[..., :dr].reshape(L, D, -1),
                  "k_r": wk[..., :dr].reshape(L, D, -1)}
        if cfg.attn_gate:
            wg = layers.pop("wg")
            groups["g"] = jnp.pad(wg, ((0, 0), (0, 0), (0, -H % 128)))
        layers["wqkv"] = jnp.concatenate(
            [groups[k] for k in _mixed_widths(cfg, name)], axis=-1)
        out[name] = layers
    return out


def _split_mixed(params, cfg):
    out = dict(params)
    for name in _MIXED_STACKS:
        if name not in params:
            continue
        layers = dict(params[name])
        KVH, _, _, _, H, _ = cfg.attention_kind(name)
        w = layers.pop("wqkv")
        L, D, _ = w.shape
        parts = dict(zip(_mixed_widths(cfg, name),
                         jnp.split(w, _mixed_ends(cfg, name), axis=-1)))

        def join(r, n, heads):
            if n is None:
                return r
            return jnp.concatenate(
                [r.reshape(L, D, heads, -1), n.reshape(L, D, heads, -1)],
                axis=-1).reshape(L, D, -1)

        out[name] = dict(layers, wv=parts["v"],
                         wq=join(parts["q_r"], parts.get("q_n"), H),
                         wk=join(parts["k_r"], parts.get("k_n"), KVH),
                         **({"wg": parts["g"][..., :H]} if "g" in parts
                            else {}))
    return out


def _fuse_latent(params, cfg):
    """A latent-attention model as the serving programs take it: each
    stack's `w_ukv` `[L, rkv, H * (dn + dv)]` cut ONCE into `w_uk` `[L, rkv,
    H, dn]` and `w_uv` `[L, rkv, H, dv]`, and its `w_uq` `[L, rq, H * (dn +
    dr)]` into `w_uq_n` `[L, rq, H * dn]` and `w_uq_r` `[L, rq, H * dr]`. A
    decode step multiplies by each apart (`latent_attention_inputs`), and a
    slice of a joined matrix inside the program is a copy of it every layer
    of every step: of `w_uq`, cut at 128 of a head's 192 columns, 75 MB a
    layer a step at DeepSeek-V3's widths, after a copy of the whole stack
    at every call (0.5 of a 13.5 ms step; PERF.md, PR 39)."""
    H, dn = cfg.n_heads, cfg.qk_nope_dim
    out = dict(params)
    for name in _LATENT_STACKS:
        if name in params:
            layers = dict(params[name])
            w = layers.pop("w_ukv")
            w = w.reshape(*w.shape[:2], H, -1)
            layers["w_uk"] = w[..., :dn]
            layers["w_uv"] = w[..., dn:]
            w = layers.pop("w_uq")
            w = w.reshape(*w.shape[:2], H, -1)
            layers["w_uq_n"] = w[..., :dn].reshape(*w.shape[:2], -1)
            layers["w_uq_r"] = w[..., dn:].reshape(*w.shape[:2], -1)
            out[name] = layers
    return out


def _split_latent(params):
    out = dict(params)
    for name in _LATENT_STACKS:
        if name in params:
            layers = dict(params[name])
            L, r, H, _ = layers["w_uk"].shape
            layers["w_ukv"] = jnp.concatenate(
                [layers.pop("w_uk"), layers.pop("w_uv")], -1).reshape(L, r, -1)
            rq = layers["w_uq_n"].shape[1]
            layers["w_uq"] = jnp.concatenate(
                [layers.pop(k).reshape(L, rq, H, -1)
                 for k in ("w_uq_n", "w_uq_r")], -1).reshape(L, rq, -1)
            out[name] = layers
    return out


def fuse_qkv(params: Dict[str, Any], cfg=None) -> Dict[str, Any]:
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
    joined. A latent-attention model (`cfg` says which) has no q, k and v
    matrices to join: its serving layout is `_fuse_latent`'s; a
    mixed-attention model's stacks by kind are joined by `_fuse_mixed`."""
    if cfg is not None and cfg.latent:
        return _fuse_latent(params, cfg)
    if cfg is not None and cfg.mixed:
        return _fuse_mixed(params, cfg)
    if cfg is not None and cfg.sala:
        return _fuse_sala(params, cfg)
    layers = dict(params["layers"])
    names = _QKV + tuple(n for n in _INDEX if n in layers)
    layers["wqkv"] = jnp.concatenate([layers.pop(k) for k in names], axis=-1)
    return dict(params, layers=layers)


def split_qkv(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """`fuse_qkv` undone: the tree a checkpoint, the train step or a plain
    reference reads, its matrices cut from the fused stack anew at every
    call (bit for bit what was fused), so nothing holds a second copy of the
    projections longer than its caller does."""
    if cfg.latent:
        return _split_latent(params)
    if cfg.mixed:
        return _split_mixed(params, cfg)
    if cfg.sala:
        return _split_sala(params, cfg)
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
    if "wq" in layers or "w_ukv" in layers:
        raise ValueError("a serving program takes `fuse_qkv(params)`: one "
                         "q/k/v projection stack, not a matrix each")
    names = tuple(k for k in ("w_gate", "w_up", "w_down") if k in layers) \
        if cfg.n_experts > 0 and "router" in layers else ()
    return ({k: v for k, v in layers.items() if k not in names},
            {k: layers[k].astype(cfg.dtype) for k in names})
