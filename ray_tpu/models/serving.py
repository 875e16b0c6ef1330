"""What Serve runs on the chip: the model's serving programs.

`serve/engine.py` is the scheduler (slots, pages, the ladder, admission,
riders, the emitter) and builds no program; this module builds them all, from
`models/block.py`'s layers and `ops/`'s kernels and caches, and imports
nothing from `serve/`.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple

# Rows of a prefill that meet the sparse feed-forward at once: its sorted
# copies are `rows x experts a token` wide (2.5 GiB of temporaries at 4,096
# rows of OLMoE's widths), so a wider bucket goes through in blocks of this
# many rows; each row is computed from itself alone, so nothing changes.
_MOE_ROWS = 4096


def prefill_core(mcfg):
    """fn(params, tokens[1, B], length) -> (first_token, ks, vs, the last
    position's logits, experts) where ks/vs are [L, B, KVH, hd] and `experts`
    is None for a dense model, else `models.block.expert_stats` of the
    prompt's tokens summed over the layers — the shared prefill pass used
    by the in-engine prefill AND the disaggregated PrefillServer (reference:
    llm/_internal/serve/deployments/prefill_decode_disagg/ — there the
    split is two vLLM pools; here both halves share one traced core). A
    model with a sparse-attention indexer adds a sixth element, its indexer
    keys [L, B, Id]; a model with state-space layers, after `experts`
    (None), its layers' final (ssm state [Lm, N, Di], convolution window
    [Lm, K - 1, Di]) after the prompt's last real token, and its ks/vs are
    those of the attention layers alone.

    RIDERS. A dense or a sparse stack's core (`core.takes_riders`; an
    indexed, a hybrid and a latent stack take nobody) also runs as
    fn(params, tokens, length, arena=(kc, vc), riders=(bt, last, pos,
    riding)): ONE decode step of the slots `riding` marks [n_slots], in the
    bucket's last n_slots rows, which the prompt has to leave free. Slot i's
    last token is embedded in row B - n_slots + i and rotated at its own
    position; per layer the tail rows' q, k and v do what a decode step does
    (the row written to the slot's page, the `paged_decode` kernel against
    the arena) and the result takes the tail of the flash output's place; the
    feed-forward and the head run over the bucket as they do anyway, so the
    step's weight reads are the prefill's. Returns (first, ks, vs, logits
    [1 + n_slots, V]: the prompt's last row, then the tail rows; experts,
    counting the riding rows; (kc, vc))."""
    if mcfg.ssm_state:
        return _make_hybrid_prefill_core(mcfg)
    if mcfg.latent:
        return _make_latent_prefill_core(mcfg)
    if mcfg.mixed:
        return _make_mixed_prefill_core(mcfg)
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (attention_inputs, expert_stacks,
                                      expert_stats, feed_forward)
    from ray_tpu.ops.attention import flash_attention, repeat_kv
    from ray_tpu.ops.norms import (apply_rope, mrope_tables, rms_norm,
                                   rope_frequencies)
    from ray_tpu.ops.paged_kv import paged_decode_attention, write_token
    from ray_tpu.ops.sparse_attention import sparse_attention

    H, KVH, hd = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim
    dt = mcfg.dtype
    sparse = mcfg.n_experts > 0
    indexed = mcfg.index_topk > 0

    def _feed_forward(lp, x, live, l):
        """`feed_forward` over at most `_MOE_ROWS` rows at a time."""
        Sq = x.shape[1]
        if not sparse or Sq <= _MOE_ROWS:
            return feed_forward(lp, x, mcfg, live, l)
        outs, counts = [], 0
        for start in range(0, Sq, _MOE_ROWS):
            rows = slice(start, start + _MOE_ROWS)
            y, (_, n) = feed_forward(lp, x[:, rows], mcfg, live[:, rows], l)
            outs.append(y)
            counts = counts + n
        return jnp.concatenate(outs, axis=1), (None, counts)

    @jax.jit
    def _token_step(kc, vc, l, bt, w, act, q, k, v):
        """One token a slot against the cache: a step's k and v `[n_slots,
        kv_heads, hd]` written at the slots' positions `w`, then each active
        slot's q `[n_slots, heads, hd]` against its positions 0..w (an idle
        slot reads nothing). A jit of its own, and ONE for the riders and for
        the decode program's layers (`build_programs` takes it from
        `core.token_step`): no prefill width enters its shapes, so the kernel
        is traced once a process, not once a riding rung and again for
        decode (a second of every start, each, on the chip's host: PERF.md
        section 6, PR 41)."""
        kc, vc = write_token(kc, vc, l, bt, w, act, k, v)
        with jax.named_scope("attn"):
            attn = paged_decode_attention(q, kc, vc, l, bt,
                                          jnp.where(act, w + 1, 0))
            attn = attn.reshape(q.shape[0], H * hd)
        return kc, vc, attn

    def _prefill_layer(stacks, riders, carry, layer):
        # `rest`: an indexed stack's own rotary tables or, with riders (an
        # indexed stack takes none), the arena, which rides the carry as it
        # does in decode (`build_programs`' `_step` says why).
        x, cos, sin, live, *rest = carry
        lp, l = layer if sparse or riders else (layer, None)
        lp = dict(lp, **stacks)
        B, Sq, _ = x.shape
        q, k, v, *index = attention_inputs(
            lp, x, mcfg, lambda t: apply_rope(t, cos, sin),
            (lambda t: apply_rope(t, *rest)) if indexed else None)
        with jax.named_scope("attn"):
            if indexed:
                qi, ki, w = index[0]
                attn = sparse_attention(q, k, v, qi.transpose(0, 2, 1, 3),
                                        ki[:, 0], w, mcfg.index_topk)
            else:
                attn = flash_attention(q, repeat_kv(k, H // KVH),
                                       repeat_kv(v, H // KVH), True)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, H * hd)
        if riders:
            bt, w, act = riders
            tail = slice(Sq - act.shape[0], Sq)
            *rest, rode = _token_step(
                *rest, l, bt, w, act, *(t[0, :, tail].transpose(1, 0, 2)
                                        for t in (q, k, v)))
            attn = attn.at[0, tail].set(
                jnp.where(act[:, None], rode, attn[0, tail]))
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        x, routed = _feed_forward(lp, x, live, l if sparse else None)
        # cache pre-repeat k/v: [S, KVH, hd] (B == 1 squeezed)
        ys = (k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))
        if sparse:
            ys += (expert_stats(routed[1]),)
        if indexed:
            ys += (ki[0, 0],)                                  # [S, Id]
        return (x, cos, sin, live, *rest), ys

    def core(params, tokens, length, arena=None, riders=None):
        if riders is not None:
            return riding_core(params, tokens, length, arena, riders)
        width = tokens.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        with jax.named_scope("rope"):
            cos, sin = rope_frequencies(hd, width, mcfg.rope_theta)
            if mcfg.mrope_section:      # text: the three streams are equal
                cos, sin = mrope_tables(
                    cos, sin, jnp.broadcast_to(jnp.arange(width), (3, width)),
                    mcfg.mrope_section)
            itables = rope_frequencies(mcfg.index_head_dim, width,
                                       mcfg.rope_theta) if indexed else ()
        # The bucket's padding is computed like any row, each from itself
        # alone (no capacity for it to take), and left out of the count.
        live = (jnp.arange(width)[None] < length) if sparse else None
        # The experts' stacks stay whole (`expert_stacks`): the scan slices
        # the rest, and carries the layer's index for them.
        sliced, stacks = expert_stacks(params["layers"], mcfg)
        if sparse:
            sliced = (sliced, jnp.arange(mcfg.n_layers))
        with jax.named_scope("layers"):
            (x, *_), (ks, vs, *more) = jax.lax.scan(
                functools.partial(_prefill_layer, stacks, None),
                (x, cos, sin, live, *itables), sliced)
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = jnp.einsum("bd,dv->bv", last_h,
                                params["lm_head"].astype(dt))
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        experts = jnp.sum(more[0], axis=0) if sparse else None
        out = (first, ks, vs, logits[0].astype(jnp.float32), experts)
        return out + ((more[-1],) if indexed else ())

    def riding_core(params, tokens, length, arena, riders):
        """`core` with the live slots in the bucket's tail rows (see
        `prefill_core`). A row that does not ride is the prompt's or
        padding, as without riders."""
        bt, last, pos, riding = riders
        width, ns, S = tokens.shape[1], riding.shape[0], mcfg.max_seq
        tail = slice(width - ns, width)
        act = riding & (pos < S)
        w = jnp.minimum(pos, S - 1)
        rows = jnp.arange(width)
        with jax.named_scope("embed"):
            tokens = tokens.at[0, tail].set(
                jnp.where(act, last, tokens[0, tail]))
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        with jax.named_scope("rope"):
            # A row's own position: the prompt's run 0.., a rider's is its
            # slot's, anywhere under max_seq.
            at = rows.at[tail].set(jnp.where(act, w, rows[tail]))
            cos, sin = (t[at] for t in
                        rope_frequencies(hd, S, mcfg.rope_theta))
        live = ((rows < length) | jnp.zeros(width, bool).at[tail].set(act)
                )[None] if sparse else None
        sliced, stacks = expert_stacks(params["layers"], mcfg)
        with jax.named_scope("layers"):
            (x, _, _, _, kc, vc), (ks, vs, *more) = jax.lax.scan(
                functools.partial(_prefill_layer, stacks, (bt, w, act)),
                (x, cos, sin, live, *arena),
                (sliced, jnp.arange(mcfg.n_layers)))
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = jnp.einsum(
                "bd,dv->bv", jnp.concatenate([last_h, x[0, tail]]),
                params["lm_head"].astype(dt))
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        experts = jnp.sum(more[0], axis=0) if sparse else None
        return (first, ks, vs, logits.astype(jnp.float32), experts, (kc, vc))

    core.takes_riders = not indexed
    core.token_step = _token_step
    return core


def _make_hybrid_prefill_core(mcfg):
    """`prefill_core` for a hybrid stack: the segments in order, a scan
    over each run of state-space layers (the stacks stay whole, the body reads
    its layer by index, as a scan reads its `xs`) and each attention layer
    inline. Rows past `length` reach no real row: attention and the
    convolution are causal, and the state-space layers are told `length`."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (attention_inputs, feed_forward,
                                      mamba_mixer)
    from ray_tpu.ops.attention import flash_attention, repeat_kv
    from ray_tpu.ops.norms import apply_rope, rms_norm, rope_frequencies

    if mcfg.n_experts or mcfg.index_topk:
        raise NotImplementedError(
            "a hybrid stack serves a dense feed-forward and plain attention")
    H, KVH, hd = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim
    dt = mcfg.dtype

    def attention_layer(lp, x, rope):
        B, Sq, _ = x.shape
        q, k, v = attention_inputs(lp, x, mcfg, rope)
        with jax.named_scope("attn"):
            attn = flash_attention(q, repeat_kv(k, H // KVH),
                                   repeat_kv(v, H // KVH), True)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, H * hd)
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        x, _ = feed_forward(lp, x, mcfg)
        return x, k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2)

    def core(params, tokens, length):
        if "wqkv" not in params["layers"]:
            raise ValueError("a serving program takes `fuse_qkv(params)`")
        width = tokens.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        if mcfg.rope:
            with jax.named_scope("rope"):
                cos, sin = rope_frequencies(hd, width, mcfg.rope_theta)
            rope = lambda t: apply_rope(t, cos, sin)
        else:
            rope = lambda t: t

        def mamba_layer(x, i):
            lp = _layer_of(params["mamba"], i)
            y, state, window = mamba_mixer(lp, x[0], mcfg, length=length)
            y, _ = feed_forward(lp, y[None], mcfg)
            return y, (state, window)

        ks, vs, states, windows = [], [], [], []
        with jax.named_scope("layers"):
            for kind, lo, hi in mcfg.segments():
                if kind == "attn":
                    x, k, v = attention_layer(
                        _layer_of(params["layers"], lo), x, rope)
                    ks.append(k)
                    vs.append(v)
                else:
                    x, (state, window) = jax.lax.scan(
                        mamba_layer, x, jnp.arange(lo, hi))
                    states.append(state)
                    windows.append(window)
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = _head_logits(params, last_h, mcfg)
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        return (first, jnp.stack(ks), jnp.stack(vs),
                logits[0].astype(jnp.float32), None,
                (jnp.concatenate(states), jnp.concatenate(windows)))

    core.takes_riders = False
    return core


def latent_rope_tables(mcfg, width):
    """(cos, sin) [width, qk_rope_dim // 2] of a latent-attention model."""
    from ray_tpu.ops.norms import rope_frequencies, yarn_frequencies
    if mcfg.rope_yarn:
        return yarn_frequencies(mcfg.qk_rope_dim, width, mcfg.rope_theta,
                                *mcfg.rope_yarn[:4])
    return rope_frequencies(mcfg.qk_rope_dim, width, mcfg.rope_theta)


def _share_stats(counts, live, mcfg):
    """One sparse layer's routing as a latent-attention program hands it
    back, `[held + 2]` int32 that add up: `expert_stats` of the HELD experts
    (tokens per expert, then the distinct ones touched), then the
    assignments the router made of the live rows, to whichever share."""
    import jax.numpy as jnp

    from ray_tpu.models.block import expert_stats
    routed = jnp.sum(live, dtype=jnp.int32) * mcfg.top_k_experts
    return jnp.concatenate([expert_stats(counts), routed[None]])


def _make_latent_prefill_core(mcfg):
    """`prefill_core` for latent attention (MLA): the segments in order
    (`LlamaConfig.segments`: the leading dense layers, then the sparse ones),
    a scan over each; `ks` is what the cache keeps, `[L, B, rank + dr]` (the
    normed latent, then the rotated shared key), and `vs` None.
    `experts` is `_share_stats` summed over the sparse layers."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (expert_stacks, feed_forward,
                                      latent_attention_inputs)
    from ray_tpu.ops.attention import latent_flash_attention
    from ray_tpu.ops.norms import apply_rope, rms_norm

    dt = mcfg.dtype
    sparse = mcfg.n_experts > 0

    def layer_fn(stacks, tables, live, x, layer):
        lp, l = layer
        routed_layer = "router" in lp
        lp = dict(lp, **stacks)
        B, Sq, _ = x.shape
        q_n, q_r, k_n, v, c, kr = latent_attention_inputs(
            lp, x, mcfg, lambda t: apply_rope(t, *tables))
        with jax.named_scope("attn"):
            attn = latent_flash_attention(q_n, q_r, k_n, kr, v,
                                          mcfg.softmax_scale)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, -1)
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        # A share's sparse half meets a quarter of `rows x experts a token`
        # rows at once (`ops.moe._share_experts`): no blocks of `_MOE_ROWS`.
        x, routed = feed_forward(lp, x, mcfg, live,
                                 l if routed_layer else None)
        ys = (c[0], kr[0])                        # [S, rank], [S, dr]
        if routed_layer:
            ys += (_share_stats(routed[1], live, mcfg),)
        return x, ys

    def core(params, tokens, length):
        width = tokens.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        with jax.named_scope("rope"):
            tables = latent_rope_tables(mcfg, width)
        live = jnp.arange(width)[None] < length
        rows, experts = [], None
        with jax.named_scope("layers"):
            for kind, lo, hi in mcfg.segments():
                sliced, stacks = expert_stacks(params[kind], mcfg)
                x, (c, kr, *stats) = jax.lax.scan(
                    functools.partial(layer_fn, stacks, tables, live), x,
                    (sliced, jnp.arange(lo, hi)))
                rows.append(jnp.concatenate([c, kr], axis=-1))
                if stats:
                    experts = jnp.sum(stats[0], axis=0)
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = _head_logits(params, last_h, mcfg)
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        return (first, jnp.concatenate(rows), None,
                logits[0].astype(jnp.float32), experts if sparse else None)

    core.takes_riders = False
    return core


def _mixed_rope_tables(mcfg, width):
    """{stack: (cos, sin) [width, rotary_dim // 2]} of a mixed-attention
    model: each kind of attention turns at its own theta."""
    from ray_tpu.ops.norms import rope_frequencies
    return {kind: rope_frequencies(mcfg.rotary_dim, width,
                                   mcfg.attention_kind(kind)[1])
            for kind, _, _ in mcfg.segments()}


def _make_mixed_prefill_core(mcfg):
    """`prefill_core` for a stack of window and full attention layers
    (`mcfg.attn_pattern`): the segments in order (`LlamaConfig.segments`:
    `dense`, `window`, `layers`), a scan over each. `ks`, `vs` are what the
    pages keep, the FULL layers' `[Lf, B, KVH, head_dim]` (a key `[k_n ;
    k_r]`) and `[Lf, B, KVH, v_head_dim]`; after `experts` (`_share_stats`
    summed over the sparse layers) come the window layers' (ks, vs) at THEIR
    kv heads, of which a slot's ring keeps the prompt's tail
    (`slot_state.write_window_prompt`)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (expert_stacks, feed_forward,
                                      mixed_attention_inputs)
    from ray_tpu.ops.attention import mixed_flash_attention
    from ray_tpu.ops.norms import apply_rope_narrow, rms_norm

    dt = mcfg.dtype
    sparse = mcfg.n_experts > 0

    def layer_fn(kind, stacks, tables, live, x, layer):
        lp, l = layer
        routed_layer = "router" in lp
        lp = dict(lp, **stacks)
        B, Sq, _ = x.shape
        _, _, window, sink = mcfg.attention_kind(kind)
        q_n, q_r, k_n, k_r, v = mixed_attention_inputs(
            lp, x, mcfg, kind, lambda t: apply_rope_narrow(t, *tables))
        with jax.named_scope("attn"):
            # The kernel and nothing else: what a roofline counts is read
            # inside the scope that times it.
            with jax.named_scope("window_attn" if window else "full_attn"):
                attn = mixed_flash_attention(
                    q_n, q_r, k_n, k_r, v, mcfg.softmax_scale, window=window,
                    sink=lp["sink"] if sink else None)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, -1)
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        x, routed = feed_forward(lp, x, mcfg, live,
                                 l if routed_layer else None)
        ys = (jnp.concatenate([k_n, k_r], -1)[0].transpose(1, 0, 2),
              v[0].transpose(1, 0, 2))            # [S, KVH, dk], [S, KVH, dv]
        if routed_layer:
            ys += (_share_stats(routed[1], live, mcfg),)
        return x, ys

    def core(params, tokens, length):
        width = tokens.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        with jax.named_scope("rope"):
            tables = _mixed_rope_tables(mcfg, width)
        live = jnp.arange(width)[None] < length
        kept = {"full": ([], []), "window": ([], [])}
        experts = 0
        with jax.named_scope("layers"):
            for kind, lo, hi in mcfg.segments():
                sliced, stacks = expert_stacks(params[kind], mcfg)
                x, (k, v, *stats) = jax.lax.scan(
                    functools.partial(layer_fn, kind, stacks, tables[kind],
                                      live), x,
                    (sliced, jnp.arange(lo, hi)))
                ks, vs = kept["window" if kind == "window" else "full"]
                ks.append(k)
                vs.append(v)
                if stats:
                    experts = experts + jnp.sum(stats[0], axis=0)
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = _head_logits(params, last_h, mcfg)
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        (ks, vs), (kws, vws) = (tuple(jnp.concatenate(t) for t in kept[k])
                                for k in ("full", "window"))
        return (first, ks, vs, logits[0].astype(jnp.float32),
                experts if sparse else None, (kws, vws))

    core.takes_riders = False
    return core


def _layer_of(stack, i):
    """Layer `i` of a stack of layers (a leading axis on every leaf): what a
    scan over the stack hands its body, read by index."""
    import jax
    return jax.tree.map(lambda w: w[i], stack)


def _head_logits(params, h, mcfg):
    """h [rows, D] -> logits [rows, V]: the head, or the embedding transposed
    where the model ties them."""
    import jax.numpy as jnp
    if mcfg.tie_embeddings:
        return jnp.einsum("bd,vd->bv", h, params["embed"].astype(mcfg.dtype))
    return h @ params["lm_head"].astype(mcfg.dtype)


# Compile-time cap on per-request top_k (jax.lax.top_k needs a static
# width; requests asking for more sample from the best TOPK_CAP).
TOPK_CAP = 64


def sample_tokens(logits, temp, topk, keys, pos, cap=TOPK_CAP):
    """Per-slot token sampling (reference: vLLM's sampler): temperature
    + top-k via Gumbel-max over the top-`cap` logits (cap is a static
    trace-time width, min(TOPK_CAP, vocab)); temp==0 slots stay greedy.
    `keys` are per-slot base PRNG keys; folding in `pos` makes a
    request's sample stream deterministic for its (seed, position)
    regardless of slot assignment or co-tenants."""
    import jax
    import jax.numpy as jnp

    cap = min(cap, logits.shape[-1])

    def one_gumbel(key, p):
        return jax.random.gumbel(jax.random.fold_in(key, p), (cap,))

    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        vals, idxs = jax.lax.top_k(logits.astype(jnp.float32), cap)
        k_eff = jnp.where(topk > 0, jnp.minimum(topk, cap), cap)
        mask = jnp.arange(cap)[None, :] < k_eff[:, None]
        scaled = jnp.where(mask, vals / jnp.maximum(temp, 1e-6)[:, None],
                           -1e30)
        g = jax.vmap(one_gumbel)(keys, pos)
        pick = jnp.argmax(scaled + g, axis=-1)
        sampled = jnp.take_along_axis(idxs, pick[:, None], axis=1)[:, 0]
        return jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)


class Caches(NamedTuple):
    """What a model keeps between programs, ONE pytree: `Programs.empty()`
    makes it, every program takes it first after `params` and hands it back
    first, donated, and the scheduler never opens it. `kc`, `vc`: the arena of
    the layers that keep K and V under the block table (`ops/paged_kv.py`; a
    latent-attention model's arena of latent rows is `kc`, and its `vc` None).
    `ic`: the indexer keys' arena of a model with sparse attention, under the
    same block table. `state`: what a model keeps a SLOT, no pages
    (`ops/slot_state.py`): the recurrent state of state-space layers, or the
    rings of window layers. None where the model has no such cache."""
    kc: Any = None
    vc: Any = None
    ic: Any = None
    state: Any = None


class Programs(NamedTuple):
    """A model's serving programs and what a scheduler has to ask of it
    without knowing it (`build_programs`).

    `empty() -> caches`; `prefill(params, caches, pages, tokens, length, temp,
    topk, key, slot, last, pos, riders) -> (caches, first, experts[, last,
    pos, tokens])`; `decode(params, caches, bt, last, pos, active, temp, topk,
    keys) -> (caches, last, pos, out, experts)`; `adopt(caches, pages, ks, vs)
    -> caches`; `poke(last, pos, slot, first, length) -> (last, pos)`."""
    empty: Callable
    prefill: Callable
    decode: Callable
    adopt: Callable
    poke: Callable
    # Whether the prefill program of a riding rung carries the live slots.
    takes_riders: bool
    # Whether a hand-off's K and V can be adopted (`adopts`).
    adopts: bool
    # Whether a prefill writes per-slot state, so `slot` is passed.
    by_slot: bool
    # Whether the programs hand back a SHARE's routing (`_share_stats`:
    # `[held + 2]` counts) where a whole model's is `expert_stats`.
    shares: bool
    # caches -> the counters `Engine.counters()` shows of them.
    cache_bytes: Callable[[Caches], Dict[str, int]]


def adopts(mcfg) -> bool:
    """Whether a PD hand-off can carry what this model caches: K and V of
    one shape a layer, and nothing else (no indexer's keys, recurrent state,
    latent rows or window rings). A function of the configuration alone: a
    `PrefillServer` builds no engine."""
    return not (mcfg.index_topk or mcfg.ssm_state or mcfg.latent
                or mcfg.mixed)


def build_programs(mcfg, n_slots: int, chunk: int, page: int,
                   n_pages: int) -> Programs:
    """The serving programs of one model at one engine's sizes."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (attention_inputs, expert_stacks,
                                      expert_stats, feed_forward,
                                      latent_attention_inputs,
                                      latent_attention_output, mamba_mixer,
                                      mixed_attention_inputs)
    from ray_tpu.ops.norms import mrope_tables, rms_norm, rope_frequencies
    from ray_tpu.ops.paged_kv import (empty, empty_index, empty_latent,
                                      latent_rows, paged_decode_attention,
                                      paged_latent_decode, write_prompt,
                                      write_prompt_rows, write_token,
                                      write_token_rows)
    from ray_tpu.ops.slot_state import (empty_state, empty_window,
                                        layer_state, state_bytes,
                                        update_layer,
                                        window_decode_attention, write_state,
                                        write_window_prompt,
                                        write_window_token)
    from ray_tpu.ops.sparse_attention import sparse_decode_attention

    sparse = mcfg.n_experts > 0
    indexed = mcfg.index_topk > 0
    hybrid = mcfg.ssm_state > 0
    latent = mcfg.latent
    mixed = mcfg.mixed
    S = mcfg.max_seq
    H, KVH, hd = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim
    dt = mcfg.dtype
    ns = n_slots

    def empty_caches() -> Caches:
        if latent:
            return Caches(kc=empty_latent(mcfg.n_layers, n_pages, page,
                                          mcfg.latent_width, dt))
        if mixed:   # pages for the full layers, a ring a slot for the rest
            return Caches(*empty(mcfg.kv_layers, n_pages, KVH, page, hd, dt,
                                 v_head_dim=mcfg.v_head_dim),
                          state=empty_window(
                mcfg.n_layers - mcfg.kv_layers, ns, mcfg.window_kv_heads,
                mcfg.window, hd, mcfg.v_head_dim, dt))
        return Caches(
            *empty(mcfg.kv_layers, n_pages, KVH, page, hd, dt,
                   by_token=indexed),
            ic=empty_index(mcfg.n_layers, n_pages, page, mcfg.index_head_dim,
                           dt) if indexed else None,
            state=empty_state(mcfg.n_layers - mcfg.kv_layers, ns,
                              mcfg.ssm_state, mcfg.ssm_inner, mcfg.ssm_conv,
                              dt) if hybrid else None)

    def cache_bytes(caches: Caches) -> Dict[str, int]:
        if hybrid:
            return {"state_bytes": state_bytes(caches.state)}
        if latent:
            return {"latent_cache_bytes": int(caches.kc.nbytes)}
        if mixed:
            return {"full_cache_bytes": int(caches.kc.nbytes
                                            + caches.vc.nbytes),
                    "window_cache_bytes": state_bytes(caches.state)}
        return {}

    # ------------------------------------------------------------------
    # prefill: full causal pass over ONE padded prompt, k/v -> pages
    # ------------------------------------------------------------------
    _core = prefill_core(mcfg)

    def prefill(params, caches, pages, tokens, length, temp, topk, key,
                slot=None, last=None, pos=None, riders=None):
        """tokens [1, B] padded to a BUCKET width (a rung of
        `prefill_widths` — jax.jit compiles one program per bucket shape, so
        a prompt pays a prefill of about its own length, not a max_seq one);
        writes the slot's pages (and the indexer's keys, where the model has
        them; or slot `slot`'s recurrent state, overwritten by the prompt's
        final one; or its window layers' rings, by the prompt's tail),
        returns the caches, the first generated token (sampled, or greedy
        when temp == 0) and the core's `experts`.

        With `riders` = (block table, riding [ns], the slots' temp, topk,
        keys) and the slots' `last` and `pos` (the program of a riding rung,
        `rung_rides`): the core's one decode step of the riding slots, and
        after `experts` come `last` and `pos` moved by it, as a decode chunk
        of one step would leave them, and the tokens [ns] the step sampled
        (a riding slot's is its next one; the others' rows are not slots')."""
        kc, vc, ic, state = caches
        if riders is not None:
            return _riding_prefill(params, kc, vc, pages, tokens, length,
                                   temp, topk, key, last, pos, *riders)
        _, ks, vs, logits_row, experts, *iks = _core(params, tokens, length)
        kc, vc = write_prompt(kc, vc, pages, ks, vs)
        first = sample_tokens(logits_row[None],
                               jnp.asarray(temp)[None],
                               jnp.asarray(topk)[None], key[None],
                               jnp.asarray(length - 1)[None])[0]
        if indexed:
            ic = write_prompt_rows(ic, pages, iks[0])
        if hybrid:
            state = write_state(state, slot, *iks[0])
        if mixed:
            state = write_window_prompt(state, slot, length, *iks[0])
        return Caches(kc, vc, ic, state), first, experts

    def _riding_prefill(params, kc, vc, pages, tokens, length, temp, topk,
                        key, last, pos, bt, riding, temps, topks, keys):
        _, ks, vs, logits, experts, (kc, vc) = _core(
            params, tokens, length, (kc, vc), (bt, last, pos, riding))
        kc, vc = write_prompt(kc, vc, pages, ks, vs)
        # The prompt's row and the riders' through ONE sampler, each row at
        # its own temperature, key and position, as `_step` samples.
        toks = sample_tokens(
            logits, jnp.concatenate([jnp.asarray(temp)[None], temps]),
            jnp.concatenate([jnp.asarray(topk)[None], topks]),
            jnp.concatenate([key[None], keys]),
            jnp.concatenate([jnp.asarray(length - 1)[None], pos]))
        act = riding & (pos < S)
        return (Caches(kc, vc), toks[0], experts,
                jnp.where(act, toks[1:], last), jnp.where(act, pos + 1, pos),
                toks[1:])

    def adopt(caches, pages, ks, vs):
        """Write externally-prefilled k/v (a PrefillServer handoff) into
        the slot's pages."""
        kc, vc = write_prompt(caches.kc, caches.vc, pages, ks, vs)
        return caches._replace(kc=kc, vc=vc)

    # ------------------------------------------------------------------
    # decode: one token for every active slot per step, `chunk` steps
    # ------------------------------------------------------------------
    def _rope_one(x, c, s):
        # x [ns, heads, hd], c/s [ns, 1, hd//2]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
        return out.astype(x.dtype)

    def _decode_layer(x, kc, vc, ic, lp, l, bt, pos, act, cos, sin, itables):
        # x [ns, D]; kc/vc (and ic) the WHOLE arena (`ops.paged_kv`); l this
        # layer's index (traced scalar); bt the block table; a sparse model's
        # expert weights in lp are all the layers' (`expert_stacks`)
        with jax.named_scope("rope"):
            w = jnp.minimum(pos, S - 1)
            if not mcfg.rope:           # attention takes no position signal
                c = s = None
            elif mcfg.mrope_section:    # text: the three streams are equal
                c, s = mrope_tables(cos, sin, jnp.broadcast_to(w, (3, ns)),
                                    mcfg.mrope_section)
                c, s = c[:, None], s[:, None]
            else:
                c = cos[w][:, None]
                s = sin[w][:, None]
            if indexed:     # the indexer's own tables, over its own width
                ci, si = (t[w][:, None] for t in itables)
        q, k, v, *index = attention_inputs(
            lp, x, mcfg,
            (lambda t: _rope_one(t, c, s)) if mcfg.rope else (lambda t: t),
            (lambda t: _rope_one(t, ci, si)) if indexed else None)
        if _core.takes_riders:
            # The write and the kernel as the riders' step has them, traced
            # once for both (`_token_step` in `prefill_core`).
            kc, vc, attn = _core.token_step(kc, vc, l, bt, w, act, q, k, v)
        else:
            kc, vc = write_token(kc, vc, l, bt, w, act, k, v)
            if indexed:
                qi, ki, iw = index[0]
                ic = write_token_rows(ic, l, bt, w, act, ki[:, 0])
            # Each active slot's query against its positions 0..w; an idle
            # slot reads nothing.
            with jax.named_scope("attn"):
                lengths = jnp.where(act, w + 1, 0)
                if indexed:
                    attn = sparse_decode_attention(
                        q, qi, iw, kc, vc, ic, l, bt, lengths,
                        mcfg.index_topk)
                else:
                    attn = paged_decode_attention(q, kc, vc, l, bt, lengths)
                attn = attn.reshape(ns, H * hd)
        with jax.named_scope("attn_out"):
            x = x + attn @ lp["wo"].astype(dt)
        # An idle slot's row is computed like any other, from itself alone,
        # and left out of the count.
        x, routed = feed_forward(lp, x, mcfg, act, l if sparse else None)
        return x, kc, vc, ic, routed

    def _hybrid_layers(params, x, kc, vc, state, bt, pos, act):
        """One token a slot through a hybrid stack's segments: the arena's
        layer is the attention layer's ordinal, the state's the state-space
        layer's; both ride the carry (see `_step`)."""
        def mamba_layer(carry, i):
            x, state = carry
            lp = _layer_of(params["mamba"], i)
            # The state's read and its write back are the update's traffic:
            # under the scope that times the update (`scan`).
            with jax.named_scope("scan"):
                ssm, window = layer_state(state, i)
            x, ssm, window = mamba_mixer(lp, x, mcfg, ssm, window, step=True)
            with jax.named_scope("scan"):
                state = update_layer(state, i, act, ssm, window)
            x, _ = feed_forward(lp, x, mcfg)
            return (x, state), None

        for kind, lo, hi in mcfg.segments():
            if kind == "attn":
                x, kc, vc, _, _ = _decode_layer(
                    x, kc, vc, None, _layer_of(params["layers"], lo), lo, bt,
                    pos, act, None, None, ())
            else:
                (x, state), _ = jax.lax.scan(mamba_layer, (x, state),
                                             jnp.arange(lo, hi))
        return x, kc, vc, state

    def _latent_layers(params, x, kc, experts, bt, pos, act, cos, sin):
        """One token a slot through a latent-attention stack's segments, a
        scan each: the absorbed query against the slot's cached rows
        (`paged_latent_decode`), the step's own row written first. The arena
        rides the carry as K and V do (see `_step`); its layer is the
        layer's place in the whole stack."""
        w = jnp.minimum(pos, S - 1)
        lengths = jnp.where(act, w + 1, 0)
        with jax.named_scope("rope"):
            c, s = cos[w][:, None], sin[w][:, None]

        def body(stacks, base, carry, layer):
            x, kc, experts = carry
            lp, l = layer
            routed_layer = "router" in lp
            lp = dict(lp, **stacks)
            ql, q_r, row, kr = latent_attention_inputs(
                lp, x, mcfg, lambda t: _rope_one(t, c, s), absorb=True)
            kc = write_token_rows(kc, base + l, bt, w, act,
                                  latent_rows(row, kr, kc))
            with jax.named_scope("attn"):
                ol = paged_latent_decode(ql, q_r, kc, base + l, bt, lengths,
                                         sm_scale=mcfg.softmax_scale)
            with jax.named_scope("attn_out"):
                x = x + latent_attention_output(lp, ol, mcfg) \
                    @ lp["wo"].astype(dt)
            x, routed = feed_forward(lp, x, mcfg, act,
                                     l if routed_layer else None)
            if routed_layer:
                experts = experts + _share_stats(routed[1], act, mcfg)
            return (x, kc, experts), None

        base = 0
        for kind, lo, hi in mcfg.segments():
            sliced, stacks = expert_stacks(params[kind], mcfg)
            (x, kc, experts), _ = jax.lax.scan(
                functools.partial(body, stacks, base), (x, kc, experts),
                (sliced, jnp.arange(lo, hi)))
            base += hi - lo
        return x, kc, experts

    def _mixed_layers(params, x, kc, vc, state, experts, bt, pos, act,
                      tables):
        """One token a slot through the segments of a stack of window and
        full attention layers, a scan each. A full layer writes the step's
        row to the slot's page and reads its live pages in place
        (`paged_decode_attention`, the arena's layer the full layer's
        ordinal); a window layer writes it to the slot's ring and reads the
        ring alone. Both caches ride the carry (see `_step`)."""
        w = jnp.minimum(pos, S - 1)
        lengths = jnp.where(act, w + 1, 0)
        dv, scale = mcfg.v_head_dim, mcfg.softmax_scale

        def body(kind, stacks, base, c, s, carry, layer):
            x, kc, vc, state, experts = carry
            lp, l = layer
            routed_layer = "router" in lp
            lp = dict(lp, **stacks)
            _, _, window, sink = mcfg.attention_kind(kind)
            q_n, q_r, k_n, k_r, v = mixed_attention_inputs(
                lp, x, mcfg, kind, lambda t: _rope_one(t, c, s))
            q, k = (jnp.concatenate(t, -1) for t in ((q_n, q_r), (k_n, k_r)))
            if window:
                state = write_window_token(state, l, w, act, k, v)
                with jax.named_scope("attn"):
                    with jax.named_scope("window_attn"):
                        attn = window_decode_attention(
                            q, state, l, w, act, window=window,
                            sm_scale=scale, sink=lp["sink"] if sink else None)
            else:
                kc, vc = write_token(kc, vc, base + l, bt, w, act, k, v)
                with jax.named_scope("attn"):
                    with jax.named_scope("full_attn"):
                        # q in the lanes a cached key lies in: zeros meet
                        # the arena's padding
                        attn = paged_decode_attention(
                            jnp.pad(q, ((0, 0), (0, 0),
                                        (0, kc.shape[-1] - q.shape[-1]))),
                            kc, vc, base + l, bt, lengths, sm_scale=scale)
            with jax.named_scope("attn_out"):
                x = x + attn[..., :dv].astype(dt).reshape(ns, -1) \
                    @ lp["wo"].astype(dt)
            x, routed = feed_forward(lp, x, mcfg, act,
                                     l if routed_layer else None)
            if routed_layer:
                experts = experts + _share_stats(routed[1], act, mcfg)
            return (x, kc, vc, state, experts), None

        for kind, lo, hi in mcfg.segments():
            sliced, stacks = expert_stacks(params[kind], mcfg)
            with jax.named_scope("rope"):
                c, s = (t[w][:, None] for t in tables[kind])
            # the arena's layer: the leading dense layers, then `layers`
            base = mcfg.first_dense if kind == "layers" else 0
            (x, kc, vc, state, experts), _ = jax.lax.scan(
                functools.partial(body, kind, stacks, base, c, s),
                (x, kc, vc, state, experts), (sliced, jnp.arange(lo, hi)))
        return x, kc, vc, state, experts

    def _step(params, sliced, stacks, kc, vc, ic, experts, bt, last, pos,
              active, cos, sin, itables, temp, topk, keys, state=None):
        # sliced, stacks: `expert_stacks` of the layers, split (and where
        # need be cast) once a chunk, outside the loop over its steps
        act = active & (pos < S)
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], last, axis=0).astype(dt)

        def body(carry, layer):
            x, kc, vc, ic, *experts = carry
            lp, l = layer
            x, kc, vc, ic, routed = _decode_layer(
                x, kc, vc, ic, dict(lp, **stacks), l, bt, pos, act, cos, sin,
                itables)
            if sparse:
                experts = [experts[0] + expert_stats(routed[1])]
            return (x, kc, vc, ic, *experts), None

        # The arena rides this scan's CARRY, and only the page write and
        # the attention kernel's reads touch it, so the layer loop, the
        # chunk loop around it and the donated entry buffers all alias ONE
        # buffer: a step rewrites `ns` pages a layer and moves nothing else
        # (the kernel is handed the arena and `l`, never `kc[l]`: a custom
        # call given a slice is first given a copy of it). It must stay out of
        # the scan's xs/ys: an xs is read-only and a ys is a freshly
        # stacked result, so the compiler would slice every layer's slab
        # out, write it into a second arena and copy that back as the next
        # step's carry (2.9 GB a step at 12 layers x 929 pages; PERF.md,
        # PR 25). The xs are the layer's weights and its index.
        with jax.named_scope("layers"):
            if hybrid:      # segments, not one scan: `_hybrid_layers`
                x, kc, vc, state = _hybrid_layers(params, x, kc, vc, state,
                                                  bt, pos, act)
            elif latent:    # segments too: `_latent_layers`
                x, kc, stats = _latent_layers(
                    params, x, kc,
                    experts[0] if sparse else jnp.zeros((), jnp.int32), bt,
                    pos, act, cos, sin)
                experts = [stats] if sparse else []
            elif mixed:     # segments by kind: `_mixed_layers` (`cos`: the
                x, kc, vc, state, stats = _mixed_layers(    # kinds' tables)
                    params, x, kc, vc, state,
                    experts[0] if sparse else jnp.zeros((), jnp.int32), bt,
                    pos, act, cos)
                experts = [stats] if sparse else []
            else:
                (x, kc, vc, ic, *experts), _ = jax.lax.scan(
                    body, (x, kc, vc, ic, *experts),
                    (sliced, jnp.arange(mcfg.n_layers)))
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            logits = _head_logits(params, x, mcfg)         # [ns, V]
        nxt = sample_tokens(logits, temp, topk, keys, pos)
        nxt = jnp.where(act, nxt, last)
        pos2 = jnp.where(act, pos + 1, pos)
        return kc, vc, ic, experts, nxt, pos2, state

    def decode(params, caches, bt, last, pos, active, temp, topk, keys):
        """-> (caches, last, pos, tokens [ns, chunk], experts): `experts` is
        None for a dense model, else `expert_stats` of the live slots'
        tokens summed over the chunk's steps and the layers."""
        kc, vc, ic, state = caches
        cos = sin = None
        itables = ()
        if latent:
            with jax.named_scope("rope"):
                cos, sin = latent_rope_tables(mcfg, S)
        elif mixed:
            with jax.named_scope("rope"):
                cos = _mixed_rope_tables(mcfg, S)
        elif mcfg.rope:
            with jax.named_scope("rope"):
                cos, sin = rope_frequencies(hd, S, mcfg.rope_theta)
                itables = rope_frequencies(mcfg.index_head_dim, S,
                                           mcfg.rope_theta) if indexed else ()
        out0 = jnp.zeros((ns, chunk), jnp.int32)
        # `expert_stats`' width, and `_share_stats`' for a share.
        experts0 = [jnp.zeros(mcfg.n_held + 1 + (latent or mixed),
                              jnp.int32)] if sparse else []
        # A stack of segments splits each segment's (`_latent_layers`,
        # `_mixed_layers`).
        sliced, stacks = (None, None) if latent or mixed \
            else expert_stacks(params["layers"], mcfg)

        def body(i, carry):
            kc, vc, ic, state, last, pos, out, *experts = carry
            kc, vc, ic, experts, nxt, pos, state = _step(
                params, sliced, stacks, kc, vc, ic, experts, bt, last, pos,
                active, cos, sin, itables, temp, topk, keys, state)
            out = out.at[:, i].set(nxt)
            return (kc, vc, ic, state, nxt, pos, out, *experts)

        kc, vc, ic, state, last, pos, out, *experts = jax.lax.fori_loop(
            0, chunk, body, (kc, vc, ic, state, last, pos, out0, *experts0))
        return (Caches(kc, vc, ic, state), last, pos, out,
                experts[0] if sparse else None)

    def poke(last, pos, slot, first, length):
        """Admission bookkeeping ON DEVICE: set one slot's (last, pos).
        Keeps the decode chain free of device->host fetches — a host
        read of last/pos at admission would cost a device round-trip
        before the TTFT token could be emitted."""
        return last.at[slot].set(first), pos.at[slot].set(length)

    # Donated: the caches, and the slots' `last` and `pos`.
    return Programs(
        empty=empty_caches,
        prefill=jax.jit(prefill, donate_argnums=(1, 9, 10)),
        decode=jax.jit(decode, donate_argnums=(1, 3, 4)),
        adopt=jax.jit(adopt, donate_argnums=(0,)),
        poke=jax.jit(poke, donate_argnums=(0, 1)),
        takes_riders=_core.takes_riders, adopts=adopts(mcfg),
        by_slot=hybrid or mixed, shares=latent or mixed,
        cache_bytes=cache_bytes)
