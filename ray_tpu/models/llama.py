"""Llama-family decoder-only transformer — the framework's flagship model.

Pure-functional JAX: parameters are a plain pytree with a parallel pytree of
*logical axis* tuples (see ray_tpu.parallel.sharding); no NN framework layer in
between, so GSPMD sharding, pipelining, and remat act on explicit structures.

Parallelism composition (all driven by ParallelContext):
  * dp/fsdp  — batch sharding + GSPMD parameter sharding via logical rules
  * tp       — Megatron-style hidden-dim sharding via logical rules
  * sp       — ring attention over the sp axis (manual shard_map region)
  * pp       — GPipe microbatch schedule (ray_tpu.parallel.pipeline)
  * ep       — MoE expert sharding (n_experts > 0)

The reference framework carries no model code of its own (models live in
engines it orchestrates); this model is the workload its north-star targets
(BASELINE.json: Llama-2-7B DDP ≥40% MFU on v5e-16).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models.block import attention_inputs, feed_forward
from ray_tpu.ops.attention import (flash_attention, pallas_eligible,
                                   repeat_kv)
from ray_tpu.ops.moe import up_out_in
from ray_tpu.ops.norms import (apply_rope, mrope_tables, rms_norm,
                               rope_frequencies, yarn_frequencies)
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.linear_attention import decay_rates
from ray_tpu.ops.sparse_attention import BlockSparse, sparse_attention
from ray_tpu.parallel.context import ParallelContext


class AttentionKind(NamedTuple):
    """One kind of a mixed-attention model's layers
    (`LlamaConfig.attention_kind`)."""
    kv_heads: int
    theta: float
    window: int
    sink: bool
    heads: int
    rotary_dim: int


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    # MoE: 0 experts = dense FFN in every layer; d_ff is then ONE expert's
    # width. norm_topk_prob: the router's weights renormalised over the
    # selected experts (Mixtral) or left as the softmax over all (OLMoE).
    n_experts: int = 0
    top_k_experts: int = 2
    norm_topk_prob: bool = True
    moe_aux_weight: float = 0.01
    # RMS norm of q and k: True, over the whole projection, before the heads
    # (OLMoE); "head", over each head's own head_dim (the Qwen3 family).
    qk_norm: Any = False
    # A head's width; 0 => d_model // n_heads.
    head_dim: int = 0
    # Multimodal RoPE (Qwen2-VL): the rotary frequencies in sections, one a
    # position stream (temporal, height, width); None => one stream.
    mrope_section: Optional[Tuple[int, ...]] = None
    # Learned sparse attention (ops/sparse_attention.py): index_topk > 0 =>
    # an indexer of index_heads heads of index_head_dim picks the index_topk
    # positions a query attends to.
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    # State-space (Mamba-1) layers among the attention layers, a hybrid
    # stack: ssm_state > 0 => every layer whose index is not in `attn_layers`
    # is a Mamba mixer (ops/ssm.py; `models.block.mamba_mixer`) of inner
    # width ssm_expand * d_model, ssm_state states a channel, a causal
    # convolution over ssm_conv inputs and a time-step projection of rank
    # ssm_dt_rank; every layer keeps its feed-forward (but under
    # `layer_parts`, below). The parameters are two stacks, `layers` (the
    # attention layers, in order) and `mamba` (the rest), so no layer holds
    # weights of the kind it is not.
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    attn_layers: Optional[Tuple[int, ...]] = None
    # ssm_heads > 0 => the state-space layers are Mamba-2's
    # (`models.block.mamba2_mixer`): ssm_heads heads of ssm_inner / ssm_heads
    # channels, ONE scalar decay, time step and D a head, B and C of
    # ssm_state numbers in `ssm_groups` groups (head h reads group h //
    # (ssm_heads / ssm_groups); one group: shared by every head), the
    # convolution over x, B and C together, a gated RMS norm (over each
    # group's channels) before the output projection; no ssm_dt_rank.
    # ssm_head_dim > 0: a head's channels, where the inner width is not
    # ssm_expand * d_model (`ssm_inner`). Either hybrid's feed-forward may be
    # sparse (n_experts > 0), a share of it held (experts_held), with shared
    # experts beside it.
    ssm_heads: int = 0
    ssm_groups: int = 1
    ssm_head_dim: int = 0
    # A hybrid whose layers are ONE part each (the Nemotron-H family):
    # `layer_parts` has a letter a layer, as published: "M" a Mamba-2 mixer
    # (ssm_heads), "E" the sparse experts (with their shared expert), "*"
    # attention; block i is h + part_i(rmsnorm(h)) and nothing else, so no
    # layer keeps a feed-forward beside its mixer. `attn_layers` is then the
    # places of the "*". The parameters are a stack a kind, none holding a
    # weight of a kind it is not: `mamba` (the mixers), `experts` (a norm, the
    # router, the experts held, the shared expert) and `layers` (attention);
    # `segments()` has the order they run in.
    layer_parts: Optional[str] = None
    # The feed-forward's expert, one of the two forms there are: "swiglu",
    # silu(x W_gate) * (x W_up) through W_down, or "relu2", relu(x W_up)^2
    # W_down (no `w_gate` leaf, the shared expert's neither), which the
    # one-part stack (`layer_parts`) serves.
    ffn: str = "swiglu"
    # False: attention takes no position signal at all (no RoPE), as in a
    # hybrid whose state-space layers carry the order of the sequence.
    rope: bool = True
    # True: the head is the embedding transposed; `lm_head` is no leaf.
    tie_embeddings: bool = False
    # Latent attention (MLA, the DeepSeek-V3 family): kv_lora_rank > 0 => q
    # goes through a latent of q_lora_rank and a norm, and a token leaves ONE
    # normed latent row of kv_lora_rank and ONE rotated key of qk_rope_dim,
    # shared by all heads, from which every head's key (qk_nope_dim, beside
    # the shared qk_rope_dim) and value (v_head_dim) are up-projected
    # (`models.block.latent_attention_inputs`). That row of kv_lora_rank +
    # qk_rope_dim numbers is all a serving cache holds
    # (`ops/paged_kv.py::empty_latent`); n_kv_heads and head_dim say nothing.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # YaRN: (factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale_all_dim) => `ops.norms.yarn_frequencies` and `softmax_scale`.
    rope_yarn: Optional[Tuple[float, ...]] = None
    # The first `first_dense` layers have a dense feed-forward of width
    # `d_ff_dense`, the rest the sparse one (n_experts > 0). Their parameters
    # are the stack `dense`; `layers` holds the sparse layers.
    first_dense: int = 0
    d_ff_dense: int = 0
    # Shared experts: a dense SwiGLU of width n_shared_experts * d_ff every
    # token meets beside its routed experts.
    n_shared_experts: int = 0
    # The router's variant (`ops.moe.top_k_routing`): "softmax", or "sigmoid"
    # with a selection bias (a leaf, `router_bias`), `n_group` groups of which
    # `topk_group` stay, and the factor `routed_scale` on the weights.
    router_score: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    # (offset, count): this program holds experts offset .. offset + count - 1
    # of the n_experts the router scores, one share of an expert-parallel
    # deployment, and computes their part of every layer's mixture
    # (`ops.moe.moe_ffn`); None: every expert.
    experts_held: Optional[Tuple[int, int]] = None
    # Two kinds of ATTENTION in one stack (the MiMo-V2 and Laguna families):
    # attn_pattern says of each layer whether it attends to every earlier
    # position (0) or to the last `window` of them, itself included (1). Both
    # kinds have keys of head_dim and values of v_head_dim; the rest is BY
    # KIND (`attention_kind`): a full layer has n_heads query heads on
    # n_kv_heads kv heads, turns the first rotary_dim numbers of a q or k
    # head at rope_theta and passes the rest, and with `rope_yarn` (its first
    # four numbers) turns at YaRN's frequencies, its cos and sin times
    # rope_magnitude (YaRN's `attention_factor`: on the turned part of a
    # logit alone, squared); a window layer has window_heads query heads (0:
    # n_heads) on window_kv_heads kv heads, turns window_rotary_dim numbers
    # (0: rotary_dim; either kind's may be the whole head) at
    # window_rope_theta, never under YaRN, and, with `window_sink`, has a
    # learned logit a head that joins every query's softmax and carries no
    # value (`sink`, a leaf). The values are multiplied by value_scale. With
    # `attn_gate` every layer has one more projection of its normed input,
    # `wg` `[d_model, the kind's query heads]`: head h's output is multiplied
    # by sigmoid(h wg)_h before `wo`. The
    # parameters are stacks by kind, none holding a weight of a kind it is
    # not: `dense` (the first_dense leading layers, full attention), `window`
    # (the window layers) and `layers` (the other full ones), `wq`, `wo` and
    # `wg` at the kind's own width; `segments()` has
    # the order they run in. A serving cache of two shapes: pages for the full
    # layers, a ring of `window` positions a slot for the window layers
    # (`ops/paged_kv.py`).
    attn_pattern: Optional[Tuple[int, ...]] = None
    window: int = 0
    window_kv_heads: int = 0
    window_rope_theta: float = 10000.0
    window_sink: bool = False
    rotary_dim: int = 0
    value_scale: float = 1.0
    window_heads: int = 0
    window_rotary_dim: int = 0
    rope_magnitude: float = 1.0
    attn_gate: bool = False
    # Gated short-convolution layers among the attention layers (the LFM2
    # family): every layer whose index is in `conv_layers` has, in the place
    # of attention, `models.block.conv_mixer`: an input projection cut in
    # three, B, C and X, a depthwise causal convolution of `conv_taps` taps
    # over B * X, the gate C and an output projection; no position signal.
    # The stack is sparse (n_experts > 0) after `first_dense` leading dense
    # layers, which are conv layers, and its router may be either variant.
    # The parameters are stacks by kind, none holding a weight of a kind it
    # is not: `dense` (a conv operator over the dense feed-forward), `conv`
    # (a conv operator over the experts) and `layers` (attention over the
    # experts); `segments()` has the order they run in. A serving cache of
    # two shapes: pages for the attention layers, and for each conv layer
    # the last `conv_taps - 1` inputs of the convolution a slot, whatever the
    # context (`ops/slot_state.py`).
    conv_layers: Optional[Tuple[int, ...]] = None
    conv_taps: int = 3
    # What the sigmoid router adds to the sum it renormalises by
    # (`ops.moe.top_k_routing`): DeepSeek-V3's 1e-20, the LFM2 family's 1e-6.
    router_norm_eps: float = 1e-20
    # The Granite family's scalar multipliers, each emitted only where it is
    # not its default: the embedding's rows times embed_scale; every residual
    # branch (a mixer's, attention's, the feed-forward's) times
    # residual_scale before its add; the logits times logit_scale (published
    # as a divisor, `logits_scaling` 16: 1/16); q . k times attn_scale in the
    # place of head_dim^-1/2 (0.0: that default). Served by the uniform and
    # the state-space hybrid stacks.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    attn_scale: float = 0.0
    # Generation by diffusion over blocks (the SDAR family): block_length > 1
    # => every attention layer's mask lets a position see ALL of its own block
    # of block_length positions, both ways, and every block before it; a
    # position predicts its OWN token; and Serve generates a block at a time:
    # denoise_steps forwards of the block's rows, each committing the most
    # confident of the rows still masked (embedded as mask_id); the committed
    # block's rows, whose K and V the cache keeps, go through the model beside
    # the next block's in that block's first forward, the two streams of one
    # forward (`models/serving.py`, the block step). Served by the uniform
    # stack of plain attention, dense or sparse; training's forward is not
    # built.
    block_length: int = 1
    denoise_steps: int = 1
    mask_id: int = 0
    # What mixes positions in the uniform stack's layers: "attention", or
    # "retention": power retention of degree `retention_degree` in EVERY
    # layer (`ops/retention.py`; the Brumby family), the block's q, k, v,
    # `qk_norm` and RoPE kept, one more projection `wg` `[d_model,
    # n_kv_heads]` (and a constant `bg` a kv head inside its logsigmoid) for
    # the gate. Such a model keeps NO K and V: `kv_layers` is 0, nothing is
    # paged, and a slot holds a state of fixed size a layer
    # (`state_layers` = n_layers; `ops/slot_state.py::empty_retention`).
    mixer: str = "attention"
    retention_degree: int = 2
    # Decayed LINEAR attention beside attention that selects BLOCKS (the
    # MiniCPM-SALA family): `mixer_types` says of each layer, in no period,
    # whether it is "minicpm4" (the SPARSE kind: grouped-query attention with
    # NO rotation over n_heads query heads on n_kv_heads kv heads, which
    # reads every earlier key while its context is under `dense_len` and from
    # there on the `sparse_topk` blocks of `sparse_block` positions that
    # score best against keys mean-pooled over `sparse_kernel` positions
    # every `sparse_stride`, the first `sparse_init_blocks` and the
    # `sparse_window` positions before its own always among them, ONE
    # selection a kv head: `ops/sparse_attention.py::BlockSparse`) or
    # "lightning-attn" (the LINEAR kind: `lightning_heads` heads of
    # `lightning_head_dim` with as many kv heads, an RMS norm over each head
    # of q and of k (`lightning_qk_norm`), RoPE at rope_theta over the whole
    # head (`lightning_rope`), and a state `[d, d]` float32 a head under a
    # constant decay of the head and of the layer's place among the
    # `published_layers` the model is published with, whatever depth is
    # built: `ops/linear_attention.py`). The sparse kind's output is
    # multiplied by sigmoid of one more projection of the block's normed
    # input, `wg` `[d_model, n_heads * head_dim]`, before `wo`
    # (`sparse_gate`); the linear kind's is RMS-normed over the joined heads
    # (`lightning_norm`, the leaf `o_norm`) and gated the same way
    # (`lightning_gate`). Every layer has the dense feed-forward; the scalar
    # multipliers apply. The parameters are stacks by kind, `sparse` and
    # `linear`, none holding a weight of a kind it is not; `segments()` has
    # the order they run in. A serving cache of three shapes: pages for the
    # sparse layers, one kv head a layer of the arena and a page a block;
    # their pooled keys a slot; the linear layers' state a slot
    # (`ops/slot_state.py`). Serving only.
    mixer_types: Optional[Tuple[str, ...]] = None
    published_layers: int = 0
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    lightning_qk_norm: bool = True
    lightning_rope: bool = True
    lightning_gate: bool = True
    lightning_norm: bool = True
    sparse_gate: bool = True
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    dense_len: int = 8192
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.float32     # master parameter dtype
    remat: bool = True
    # Rematerialization policy when remat=True: "none" (save everything the
    # scan carries anyway), "full" (recompute everything — min memory, max
    # recompute), "dots" (save every matmul output), "dots_nobatch" (save
    # weight-matmul outputs, recompute attention/elementwise — usually the
    # MFU sweet spot on TPU: HBM traffic for the big dots is avoided while
    # the recompute is cheap non-MXU work).
    remat_policy: str = "full"
    num_microbatches: int = 0          # 0 => equal to pp size

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.layer_parts is not None:
            self._check_parts()
        if self.attn_layers is not None:
            object.__setattr__(self, "attn_layers",
                               tuple(sorted(self.attn_layers)))
        if self.latent and (self.ssm_state or self.index_topk or self.qk_norm
                            or self.mrope_section or self.tie_embeddings):
            raise ValueError("latent attention (kv_lora_rank > 0) comes with "
                             "no state-space layers, indexer, q/k norm, "
                             "mrope or tied head")
        if self.attn_pattern is not None:
            object.__setattr__(self, "attn_pattern",
                               tuple(int(k) for k in self.attn_pattern))
            self._check_mixed()
        if self.conv_layers is not None:
            object.__setattr__(self, "conv_layers",
                               tuple(sorted(int(i) for i in self.conv_layers)))
            self._check_conv()
        if self.mixer_types is not None:
            object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
            self._check_sala()
        segmented = self.latent or self.mixed or self.conv
        if self.multipliers and segmented:
            raise ValueError("embed_scale, residual_scale, logit_scale and "
                             "attn_scale are served by the uniform stack and "
                             "the state-space hybrid: no latent or mixed "
                             "attention, no short-convolution layers")
        if self.first_dense and not (segmented and self.n_experts
                                     and self.first_dense < self.n_layers):
            raise ValueError("first_dense: leading dense layers under a "
                             "sparse latent-attention, mixed-attention or "
                             "short-convolution stack")
        if self.experts_held and not (segmented or self.ssm_state):
            raise ValueError("a share of the experts is served by the stacks "
                             "that run as segments: latent attention "
                             "(kv_lora_rank > 0), mixed attention "
                             "(attn_pattern) and the state-space hybrid "
                             "(ssm_state)")
        if self.router_score != "softmax" and not (segmented
                                                   or self.layer_parts):
            raise ValueError("the sigmoid router is served by four of the "
                             "stacks that run as segments: latent attention "
                             "(kv_lora_rank > 0), mixed attention "
                             "(attn_pattern), short-convolution layers "
                             "(conv_layers) and the state-space hybrid of "
                             "one-part layers (layer_parts)")
        if self.n_shared_experts and not (
                self.n_experts and (self.latent or self.ssm_state
                                    or self.mixed)):
            raise ValueError("shared experts are served beside sparse "
                             "experts, by the latent-attention stack "
                             "(kv_lora_rank > 0), the mixed-attention stack "
                             "(attn_pattern) and the state-space hybrid "
                             "(ssm_state)")
        if not self.mixed and (self.window_heads or self.window_rotary_dim
                               or self.attn_gate
                               or self.rope_magnitude != 1.0):
            raise ValueError("window_heads, window_rotary_dim, attn_gate and "
                             "rope_magnitude are a mixed-attention stack's "
                             "(attn_pattern)")
        if bool(self.ssm_state) != (self.attn_layers is not None):
            raise ValueError("ssm_state and attn_layers come together: the "
                             "state-space widths and which layers are not "
                             "state-space layers")
        if self.ssm_heads and (not self.ssm_state
                               or self.ssm_inner % self.ssm_heads):
            raise ValueError("ssm_heads: Mamba-2's heads, of ssm_inner / "
                             "ssm_heads channels each, in a hybrid stack "
                             "(ssm_state, attn_layers)")
        if self.ssm_groups < 1 or (self.ssm_groups > 1 and (
                not self.ssm_heads or self.ssm_heads % self.ssm_groups)):
            raise ValueError("ssm_groups: groups of B and C, each read by "
                             "ssm_heads / ssm_groups of Mamba-2's heads")
        if self.ssm_head_dim and not self.ssm_heads:
            raise ValueError("ssm_head_dim: the channels of one of Mamba-2's "
                             "heads (ssm_heads)")
        if self.ffn != "swiglu" and (self.layer_parts is None
                                     or self.ffn != "relu2"):
            raise ValueError("ffn 'swiglu' or 'relu2': a feed-forward other "
                             "than the gated silu one is served as the "
                             "experts of a stack of one-part layers "
                             "(layer_parts)")
        if self.ssm_state and self.index_topk:
            raise ValueError("a state-space hybrid has plain attention: no "
                             "indexer")
        if self.mixer != "attention":
            self._check_retention()
        if self.block_length > 1:
            self._check_block()
        elif self.block_length < 1 or self.denoise_steps != 1:
            raise ValueError("block_length is 1 (one token a step) or more; "
                             "denoise_steps come with a block")
        if self.experts_held:
            offset, count = self.experts_held
            if not 0 <= offset < offset + count <= self.n_experts:
                raise ValueError("experts_held: (offset, count) inside the "
                                 "router's n_experts")

    def _check_parts(self) -> None:
        parts = self.layer_parts
        if len(parts) != self.n_layers or set(parts) - set("ME*"):
            raise ValueError("layer_parts: one of 'M' (a Mamba-2 mixer), 'E' "
                             "(the experts) or '*' (attention) a layer")
        if self.attn_layers is not None:
            raise ValueError("layer_parts says which layers are attention: "
                             "no attn_layers beside it")
        if not (self.ssm_state and self.ssm_heads and self.n_experts):
            raise ValueError("layer_parts: a stack of one-part layers has "
                             "Mamba-2 mixers (ssm_state, ssm_heads) and "
                             "sparse experts (n_experts > 0)")
        if self.multipliers:
            raise ValueError("embed_scale, residual_scale, logit_scale and "
                             "attn_scale are not served by the stack of "
                             "one-part layers (layer_parts)")
        object.__setattr__(self, "attn_layers", tuple(
            i for i, part in enumerate(parts) if part == "*"))

    def _check_mixed(self) -> None:
        if self.latent or self.ssm_state or self.index_topk or self.qk_norm \
                or self.mrope_section or self.tie_embeddings \
                or not self.rope:
            raise ValueError("mixed attention (attn_pattern) comes with no "
                             "latent attention, state-space layers, indexer, "
                             "q/k norm, mrope or tied head")
        if self.rope_yarn and len(self.rope_yarn) < 4:
            raise ValueError("rope_yarn: (factor, original_max, beta_fast, "
                             "beta_slow) for the full-attention layers")
        pattern = self.attn_pattern
        if len(pattern) != self.n_layers or set(pattern) - {0, 1}:
            raise ValueError("attn_pattern: one of 0 (full) or 1 (window) a "
                             "layer")
        if any(pattern[:self.first_dense]):
            raise ValueError("attn_pattern: the first_dense leading layers "
                             "are full-attention layers (the stack `dense`)")
        if 1 in pattern and not (self.window > 0
                                 and self.window_kv_heads > 0):
            raise ValueError("attn_pattern has window layers: window and "
                             "window_kv_heads say their size")
        if not self.v_head_dim:
            object.__setattr__(self, "v_head_dim", self.head_dim)
        for kind in ("layers", "window"):
            _, _, _, _, heads, rotary = self.attention_kind(kind)
            if rotary % 2 or not 0 < rotary <= self.head_dim:
                raise ValueError(
                    "rotary_dim, window_rotary_dim: mixed attention rotates "
                    "an even part of a head, 0 < rotary_dim <= head_dim")
            if heads % self.attention_kind(kind).kv_heads:
                raise ValueError("n_heads, window_heads: whole groups of "
                                 "query heads a kv head, by kind")
        if self.attn_gate and self.window_sink:
            raise ValueError("attn_gate with window_sink: no published "
                             "model has both, and none is computed")

    def _check_retention(self) -> None:
        if self.mixer != "retention":
            raise ValueError("mixer: 'attention' or 'retention'")
        if self.latent or self.mixed or self.conv or self.ssm_state \
                or self.index_topk or self.mrope_section or self.n_experts \
                or self.multipliers or self.block_length > 1 \
                or not self.rope:
            raise ValueError("power retention (mixer 'retention') is served "
                             "in the uniform dense stack under RoPE: no "
                             "latent or mixed attention, state-space or "
                             "short-convolution layers, indexer, mrope, "
                             "experts, multipliers or generation by blocks")
        if self.retention_degree != 2:
            raise ValueError("retention_degree: the expansion this program "
                             "builds is the degree-2 one (ops/retention.py)")
        if self.head_dim % 2 or self.n_heads % self.n_kv_heads:
            raise ValueError("power retention: an even head_dim, and whole "
                             "groups of query heads a kv head")

    def _check_sala(self) -> None:
        if self.latent or self.mixed or self.conv or self.ssm_state \
                or self.index_topk or self.mrope_section or self.n_experts \
                or self.retention or self.block_length > 1 or self.qk_norm \
                or self.tie_embeddings or self.attn_scale:
            raise ValueError("linear and block-sparse layers (mixer_types) "
                             "come with the dense feed-forward and an untied "
                             "head: no latent or mixed attention, "
                             "state-space, short-convolution or retention "
                             "layers, indexer, mrope, experts, generation by "
                             "blocks, q/k norm in the sparse layers or "
                             "attn_scale")
        kinds = self.mixer_types
        if len(kinds) != self.n_layers \
                or set(kinds) - {"minicpm4", "lightning-attn"}:
            raise ValueError("mixer_types: one of 'minicpm4' (block-sparse "
                             "attention) or 'lightning-attn' (linear "
                             "attention) a layer")
        if not self.published_layers:
            object.__setattr__(self, "published_layers", self.n_layers)
        if self.published_layers < self.n_layers:
            raise ValueError("published_layers: the depth the decay and the "
                             "residual scale are published for, at least "
                             "n_layers")
        if not self.lightning_heads:
            object.__setattr__(self, "lightning_heads", self.n_heads)
        if not self.lightning_head_dim:
            object.__setattr__(self, "lightning_head_dim", self.head_dim)
        if self.lightning_head_dim % 2:
            raise ValueError("lightning_head_dim: RoPE turns pairs")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads: whole groups of query heads a kv head")
        b = self.block_sparse
        if b.kernel != 2 * b.stride or b.block % b.stride \
                or b.window % b.block or b.dense_len % b.block \
                or self.max_seq % b.block:
            raise ValueError(
                "the sparse sizes: sparse_kernel is two sparse_stride, and "
                "sparse_block divides into strides and divides sparse_window, "
                "dense_len and max_seq")
        if b.init_blocks + b.window_blocks > b.topk or b.topk < 1:
            raise ValueError("sparse_topk: at least the sparse_init_blocks "
                             "and the sparse_window's blocks, which every "
                             "query reads")
        if self.rope and "minicpm4" in kinds:
            raise ValueError("rope: the sparse layers take no rotation "
                             "(rope False; the linear layers' is "
                             "lightning_rope)")

    def _check_block(self) -> None:
        if self.latent or self.mixed or self.conv or self.ssm_state \
                or self.index_topk or self.mrope_section or self.retention \
                or self.tie_embeddings or self.multipliers or not self.rope:
            raise ValueError("generation by blocks (block_length > 1) is "
                             "served by the uniform stack of plain attention "
                             "under RoPE: no latent or mixed attention, "
                             "state-space or short-convolution layers, "
                             "indexer, mrope, tied head or multipliers")
        if not 1 <= self.denoise_steps <= self.block_length:
            raise ValueError("denoise_steps: 1..block_length forwards commit "
                             "a block's rows, one at least a forward")
        if self.max_seq % self.block_length \
                or 128 % self.block_length:
            raise ValueError("block_length divides max_seq, and the kernels' "
                             "tiles and the cache's pages (a divisor of 128)")
        if not 0 <= self.mask_id < self.vocab_size:
            raise ValueError("mask_id: a row of the embedding")

    def _check_conv(self) -> None:
        if self.latent or self.mixed or self.ssm_state or self.index_topk \
                or self.mrope_section or self.rope_yarn:
            raise ValueError("short-convolution layers (conv_layers) come "
                             "with plain attention: no latent or mixed "
                             "attention, state-space layers, indexer, mrope "
                             "or YaRN")
        conv = self.conv_layers
        if not conv or len(set(conv)) != len(conv) \
                or not 0 <= conv[0] <= conv[-1] < self.n_layers \
                or len(conv) == self.n_layers:
            raise ValueError("conv_layers: distinct indices under n_layers, "
                             "some layer a conv layer and some attention")
        if not self.n_experts or self.experts_held:
            raise ValueError("short-convolution layers (conv_layers) are "
                             "served over sparse experts, every one held "
                             "(n_experts > 0, no experts_held): the stacks "
                             "`dense`, `conv` and `layers`")
        if set(range(self.first_dense)) - set(conv):
            raise ValueError("conv_layers: the first_dense leading layers "
                             "are conv layers (the stack `dense`)")
        if self.first_dense and not self.d_ff_dense:
            raise ValueError("first_dense: d_ff_dense says the dense "
                             "feed-forward's width")
        if self.conv_taps < 2:
            raise ValueError("conv_taps: a convolution over two inputs at "
                             "least")

    @property
    def mixed(self) -> bool:
        """Window and full attention in one stack (`attn_pattern`)."""
        return self.attn_pattern is not None

    @property
    def conv(self) -> bool:
        """Gated short-convolution layers beside attention (`conv_layers`)."""
        return self.conv_layers is not None

    def attention_kind(self, stack: str) -> "AttentionKind":
        """(kv heads, rope theta, window, sink, query heads, rotary width) of
        the layers of one stack of a mixed-attention model: `window`, or a
        full-attention one (`dense`, `layers`), whose window is 0."""
        if stack == "window":
            return AttentionKind(
                self.window_kv_heads, self.window_rope_theta, self.window,
                self.window_sink, self.window_heads or self.n_heads,
                self.window_rotary_dim or self.rotary_dim)
        return AttentionKind(self.n_kv_heads, self.rope_theta, 0, False,
                             self.n_heads, self.rotary_dim)

    def rope_tables(self, stack: str, n: int):
        """(cos, sin) `[n, rotary width // 2]` of one kind of a
        mixed-attention model's layers: the window kind's plain, the full
        kind's YaRN's times `rope_magnitude` where the model has them."""
        kind = self.attention_kind(stack)
        if stack == "window" or not self.rope_yarn:
            return rope_frequencies(kind.rotary_dim, n, kind.theta)
        cos, sin = yarn_frequencies(kind.rotary_dim, n, kind.theta,
                                    *self.rope_yarn[:4])
        return cos * self.rope_magnitude, sin * self.rope_magnitude

    @property
    def retention(self) -> bool:
        """Power retention in every layer (`mixer`)."""
        return self.mixer == "retention"

    @property
    def sala(self) -> bool:
        """Linear layers beside block-sparse ones (`mixer_types`)."""
        return self.mixer_types is not None

    @property
    def block_sparse(self):
        """The sparse layers' sizes (`ops.sparse_attention.BlockSparse`)."""
        return BlockSparse(self.sparse_kernel, self.sparse_stride,
                           self.sparse_block, self.sparse_topk,
                           self.sparse_init_blocks, self.sparse_window,
                           self.dense_len)

    def linear_rates(self):
        """`[linear layers, lightning_heads]` float32: each linear layer's
        decay rates, by its place in the published stack
        (`ops.linear_attention.decay_rates`)."""
        return np.stack([
            decay_rates(self.lightning_heads, i, self.published_layers)
            for i, kind in enumerate(self.mixer_types)
            if kind == "lightning-attn"])

    @property
    def kv_layers(self) -> int:
        """Layers that keep K and V under the block table: the attention
        layers, of a mixed-attention stack the full-attention ones; none of
        a stack of retention layers."""
        if self.retention:
            return 0
        if self.sala:
            return self.mixer_types.count("minicpm4")
        if self.mixed:
            return self.attn_pattern.count(0)
        if self.conv:
            return self.n_layers - len(self.conv_layers)
        return self.n_layers if self.attn_layers is None \
            else len(self.attn_layers)

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Numbers a token leaves in a latent cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def n_held(self) -> int:
        """Experts whose weights this program holds."""
        return self.experts_held[1] if self.experts_held else self.n_experts

    @property
    def ffn_gated(self) -> bool:
        return self.ffn == "swiglu"

    @property
    def ffn_act(self) -> str:
        """`ops.moe.activation`'s name for the form's."""
        return "silu" if self.ffn == "swiglu" else self.ffn

    @property
    def up_out_in(self) -> bool:
        """The experts' up and gate matrices are held `[d_ff, d_model]`: the
        one-part stack draws them by `ops.moe.up_out_in`'s rule, from the
        width; the older stacks hold `[in, out]` whatever theirs."""
        return self.layer_parts is not None and up_out_in(self.d_ff)

    @property
    def softmax_scale(self) -> float:
        """What attention multiplies q . k by: head width^-1/2 (or
        `attn_scale`, where the model publishes its own), times YaRN's m^2, m
        = 0.1 mscale_all_dim ln(factor) + 1."""
        if not self.latent:
            return self.attn_scale or self.head_dim ** -0.5
        scale = (self.qk_nope_dim + self.qk_rope_dim) ** -0.5
        if self.rope_yarn:
            factor, *_, mscale_all_dim = self.rope_yarn
            scale *= (0.1 * mscale_all_dim * math.log(factor) + 1.0) ** 2
        return scale

    def routing(self) -> Optional[Dict[str, Any]]:
        """`ops.moe.moe_ffn`'s `routing` but the bias, which is a leaf; None
        for the softmax router with no factor on its weights."""
        if self.router_score == "softmax":
            return dict(scale=self.routed_scale) \
                if self.routed_scale != 1.0 else None
        return dict(score=self.router_score, n_group=self.n_group,
                    topk_group=self.topk_group, scale=self.routed_scale,
                    norm_eps=self.router_norm_eps)

    @property
    def ssm_inner(self) -> int:
        """The state-space layers' inner width Di: Mamba-2's heads times a
        head's channels where the model says them, else ssm_expand x
        d_model."""
        if self.ssm_head_dim:
            return self.ssm_heads * self.ssm_head_dim
        return self.ssm_expand * self.d_model

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state a slot: the state-space ones,
        or every layer of a stack of retention layers."""
        if self.retention:
            return self.n_layers
        if self.sala:
            return self.mixer_types.count("lightning-attn")
        if self.layer_parts is not None:
            return self.layer_parts.count("M")
        return self.n_layers - self.kv_layers if self.ssm_state else 0

    @property
    def sparse_layers(self) -> int:
        """Layers that have a router and experts: what a program's routing
        counts are summed over."""
        if self.layer_parts is not None:
            return self.layer_parts.count("E")
        return self.n_layers - self.first_dense if self.n_experts else 0

    @property
    def multipliers(self) -> bool:
        """Whether any of the four scalar multipliers is off its default."""
        return (self.embed_scale, self.residual_scale, self.logit_scale,
                self.attn_scale) != (1.0, 1.0, 1.0, 0.0)

    @property
    def ssm_conv_channels(self) -> int:
        """Channels the state-space layers' convolution runs over: the inner
        width, and under Mamba-2 every group's B and C beside it."""
        return self.ssm_inner + (2 * self.ssm_groups * self.ssm_state
                                 if self.ssm_heads else 0)

    def segments(self) -> Tuple[Tuple[str, int, int], ...]:
        """The stack in the order it runs, each segment a kind and ordinals
        lo..hi-1 into that kind's stack of parameters. A uniform stack is one
        segment, ("layers", 0, n_layers). A hybrid: ("mamba", lo, hi), a run
        of state-space layers in `mamba`, or ("attn", a, a + 1), one
        attention layer in `layers`; a hybrid of one-part layers
        (`layer_parts`) likewise, ("experts", lo, hi) a run of expert layers
        in `experts` beside them.
        A latent-attention stack, each kind the name of its stack:
        ("dense", 0, first_dense), the leading dense layers, if it has any,
        then ("layers", 0, n_layers - first_dense), the rest. A
        mixed-attention stack likewise, its kinds `dense`, `window` and
        `layers`: each run of layers of one stack a segment. A stack with
        short-convolution layers the same way, its kinds `dense`, `conv` and
        `layers`; one of linear and block-sparse layers (`mixer_types`) too,
        its kinds `sparse` and `linear`."""
        if self.sala:
            names = {"minicpm4": "sparse", "lightning-attn": "linear"}
            out, at = [], dict.fromkeys(names.values(), 0)
            for kind in self.mixer_types:
                name = names[kind]
                if out and out[-1][0] == name:
                    out[-1] = (name, out[-1][1], at[name] + 1)
                else:
                    out.append((name, at[name], at[name] + 1))
                at[name] += 1
            return tuple(out)
        if self.mixed or self.conv:
            out, at = [], {"dense": 0, "window": 0, "conv": 0, "layers": 0}
            for i in range(self.n_layers):
                second = self.attn_pattern[i] if self.mixed \
                    else i in self.conv_layers
                name = "dense" if i < self.first_dense \
                    else ("window" if self.mixed else "conv") if second \
                    else "layers"
                if out and out[-1][0] == name:
                    out[-1] = (name, out[-1][1], at[name] + 1)
                else:
                    out.append((name, at[name], at[name] + 1))
                at[name] += 1
            return tuple(out)
        if self.latent:
            lead = (("dense", 0, self.first_dense),) if self.first_dense \
                else ()
            return lead + (("layers", 0, self.n_layers - self.first_dense),)
        if self.attn_layers is None:
            return (("layers", 0, self.n_layers),)
        if self.layer_parts is not None:
            names = {"M": "mamba", "E": "experts", "*": "attn"}
            out, at = [], dict.fromkeys(names.values(), 0)
            for part in self.layer_parts:
                name = names[part]
                if name != "attn" and out and out[-1][0] == name:
                    out[-1] = (name, out[-1][1], at[name] + 1)
                else:
                    out.append((name, at[name], at[name] + 1))
                at[name] += 1
            return tuple(out)
        out, a, m = [], 0, 0
        for i in range(self.n_layers):
            if i in self.attn_layers:
                out.append(("attn", a, a + 1))
                a += 1
            else:
                if out and out[-1][0] == "mamba":
                    out[-1] = ("mamba", out[-1][1], m + 1)
                else:
                    out.append(("mamba", m, m + 1))
                m += 1
        return tuple(out)

    # ---- presets ----
    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        base = dict(vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
                    n_kv_heads=8, d_ff=14336, max_seq=8192, rope_theta=500000.0)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=128, max_seq=128, dtype=jnp.float32)
        base.update(kw)
        return LlamaConfig(**base)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _latent_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    attn = {
        "attn_norm": ("layers", "embed"),
        "w_dq": ("layers", "embed", None),
        "q_norm": ("layers", None),
        "w_uq": ("layers", None, "heads"),
        "w_dkv": ("layers", "embed", None),
        "kv_norm": ("layers", None),
        "w_ukv": ("layers", None, "heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    dense = {"w_gate": ("layers", "embed", "mlp"),
             "w_up": ("layers", "embed", "mlp"),
             "w_down": ("layers", "mlp", "embed")}
    out = {"embed": ("vocab", "embed"), "final_norm": ("embed",),
           "lm_head": ("embed", "vocab")}
    if cfg.n_experts:
        out["layers"] = dict(
            attn, router=("layers", "embed", "expert"),
            w_gate=("layers", "expert", "embed", "mlp"),
            w_up=("layers", "expert", "embed", "mlp"),
            w_down=("layers", "expert", "mlp", "embed"))
        if cfg.router_score == "sigmoid":
            out["layers"]["router_bias"] = ("layers", "expert")
        if cfg.n_shared_experts:
            out["layers"].update({"ws_" + k[2:]: v for k, v in dense.items()})
    else:
        out["layers"] = dict(attn, **dense)
    if cfg.first_dense:
        out["dense"] = dict(attn, **dense)
    return out


def _mixed_stacks(cfg: LlamaConfig) -> Dict[str, Tuple[int, bool]]:
    """name -> (layers, sparse) of each stack a mixed-attention model has."""
    sparse = cfg.attn_pattern[cfg.first_dense:]
    out = {"dense": (cfg.first_dense, False),
           "window": (sparse.count(1), cfg.n_experts > 0),
           "layers": (sparse.count(0), cfg.n_experts > 0)}
    return {k: v for k, v in out.items() if v[0]}


def _mixed_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    out = {"embed": ("vocab", "embed"), "final_norm": ("embed",),
           "lm_head": ("embed", "vocab")}
    for name, (_, sparse) in _mixed_stacks(cfg).items():
        lead = ("layers", "expert") if sparse else ("layers",)
        stack = {"attn_norm": ("layers", "embed"),
                 "wq": ("layers", "embed", "heads"),
                 "wk": ("layers", "embed", "kv_heads"),
                 "wv": ("layers", "embed", "kv_heads"),
                 "wo": ("layers", "heads", "embed"),
                 "mlp_norm": ("layers", "embed"),
                 "w_gate": (*lead, "embed", "mlp"),
                 "w_up": (*lead, "embed", "mlp"),
                 "w_down": (*lead, "mlp", "embed")}
        if cfg.attention_kind(name).sink:
            stack["sink"] = ("layers", "heads")
        if cfg.attn_gate:
            stack["wg"] = ("layers", "embed", "heads")
        if sparse:
            stack["router"] = ("layers", "embed", "expert")
            if cfg.router_score == "sigmoid":
                stack["router_bias"] = ("layers", "expert")
            if cfg.n_shared_experts:
                stack.update(ws_gate=("layers", "embed", "mlp"),
                             ws_up=("layers", "embed", "mlp"),
                             ws_down=("layers", "mlp", "embed"))
        out[name] = stack
    return out


def _init_mixed(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """A mixed-attention model: a stack a kind (`_mixed_stacks`), each with
    its own kind's projections (a window layer's q, and with it `wo` and the
    gate's `wg`, have window_heads heads, its k and v window_kv_heads, and it
    alone has a `sink`), the experts' stacks holding the experts HELD, a
    shared expert beside them where the model has one. Keys from lists of
    this function's own."""
    D, V, pd = cfg.d_model, cfg.vocab_size, cfg.param_dtype
    dk, dv = cfg.head_dim, cfg.v_head_dim

    def norm(shape, k, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    top = iter(jax.random.split(key, 5))
    out = {"embed": norm((V, D), next(top)),
           "final_norm": jnp.ones((D,), pd),
           "lm_head": norm((D, V), next(top))}
    for name, (L, sparse) in _mixed_stacks(cfg).items():
        ks = iter(jax.random.split(next(top), 16))
        KVH, _, _, sink, H, _ = cfg.attention_kind(name)
        stack = {"attn_norm": jnp.ones((L, D), pd),
                 "wq": norm((L, D, H * dk), next(ks)),
                 "wk": norm((L, D, KVH * dk), next(ks)),
                 "wv": norm((L, D, KVH * dv), next(ks)),
                 "wo": norm((L, H * dv, D), next(ks)),
                 "mlp_norm": jnp.ones((L, D), pd)}
        if sink:
            # Published as a learned logit a head; drawn so that it takes a
            # share of the softmax that a wrong or missing sink would move.
            stack["sink"] = norm((L, H), next(ks), 1.0)
        lead, F = ((L, cfg.n_held), cfg.d_ff) if sparse \
            else ((L,), cfg.d_ff_dense or cfg.d_ff)
        stack.update(w_gate=norm((*lead, D, F), next(ks)),
                     w_up=norm((*lead, D, F), next(ks)),
                     w_down=norm((*lead, F, D), next(ks)))
        if sparse:
            stack["router"] = norm((L, D, cfg.n_experts), next(ks))
            if cfg.router_score == "sigmoid":
                stack["router_bias"] = norm((L, cfg.n_experts), next(ks))
            if cfg.n_shared_experts:
                stack.update(_shared_expert(cfg, L, ks, norm))
        if cfg.attn_gate:
            stack["wg"] = norm((L, D, H), next(ks))
        out[name] = stack
    return out


def _conv_stacks(cfg: LlamaConfig) -> Dict[str, Tuple[int, bool, bool]]:
    """name -> (layers, conv operator or attention, sparse) of each stack a
    model with short-convolution layers has."""
    conv = len(cfg.conv_layers) - cfg.first_dense
    out = {"dense": (cfg.first_dense, True, False),
           "conv": (conv, True, True),
           "layers": (cfg.kv_layers, False, True)}
    return {k: v for k, v in out.items() if v[0]}


def _conv_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    out = {"embed": ("vocab", "embed"), "final_norm": ("embed",)}
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    for name, (_, conv, sparse) in _conv_stacks(cfg).items():
        lead = ("layers", "expert") if sparse else ("layers",)
        stack = {"norm": ("layers", "embed"),
                 "in_proj": ("layers", "embed", "mlp"),
                 "conv_w": ("layers", None, "embed"),
                 "out_proj": ("layers", "embed", "embed")} if conv else {
                 "attn_norm": ("layers", "embed"),
                 "wq": ("layers", "embed", "heads"),
                 "wk": ("layers", "embed", "kv_heads"),
                 "wv": ("layers", "embed", "kv_heads"),
                 "wo": ("layers", "heads", "embed")}
        if not conv and cfg.qk_norm:
            per_head = cfg.qk_norm == "head"
            stack.update(
                q_norm=("layers", "head_dim" if per_head else "heads"),
                k_norm=("layers", "head_dim" if per_head else "kv_heads"))
        stack.update(mlp_norm=("layers", "embed"),
                     w_gate=(*lead, "embed", "mlp"),
                     w_up=(*lead, "embed", "mlp"),
                     w_down=(*lead, "mlp", "embed"))
        if sparse:
            stack["router"] = ("layers", "embed", "expert")
            if cfg.router_score == "sigmoid":
                stack["router_bias"] = ("layers", "expert")
        out[name] = stack
    return out


def _init_conv(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """A model with short-convolution layers: a stack a kind
    (`_conv_stacks`). A conv operator's leaves are `norm`, `in_proj` `[D, 3
    D]` (its columns B, then C, then X), `conv_w` `[taps, D]` (the channel
    axis the minor one, as `ops/ssm.py` has it; tap j meets the input `taps
    - 1 - j` positions back) and `out_proj`; an attention layer's are the
    uniform stack's, with the q and k norms. Keys from lists of this
    function's own."""
    D, H, KVH, V = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.vocab_size
    hd, pd = cfg.head_dim, cfg.param_dtype

    def norm(shape, k, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    top = iter(jax.random.split(key, 5))
    out = {"embed": norm((V, D), next(top)), "final_norm": jnp.ones((D,), pd)}
    head_key = next(top)
    if not cfg.tie_embeddings:
        out["lm_head"] = norm((D, V), head_key)
    for name, (L, conv, sparse) in _conv_stacks(cfg).items():
        ks = iter(jax.random.split(next(top), 16))
        if conv:
            stack = {"norm": jnp.ones((L, D), pd),
                     "in_proj": norm((L, D, 3 * D), next(ks)),
                     "conv_w": norm((L, cfg.conv_taps, D), next(ks),
                                    cfg.conv_taps ** -0.5),
                     "out_proj": norm((L, D, D), next(ks))}
        else:
            stack = {"attn_norm": jnp.ones((L, D), pd),
                     "wq": norm((L, D, H * hd), next(ks)),
                     "wk": norm((L, D, KVH * hd), next(ks)),
                     "wv": norm((L, D, KVH * hd), next(ks)),
                     "wo": norm((L, H * hd, D), next(ks))}
            if cfg.qk_norm:
                per_head = cfg.qk_norm == "head"
                stack.update(
                    q_norm=jnp.ones((L, hd if per_head else H * hd), pd),
                    k_norm=jnp.ones((L, hd if per_head else KVH * hd), pd))
        stack["mlp_norm"] = jnp.ones((L, D), pd)
        lead, F = ((L, cfg.n_experts), cfg.d_ff) if sparse \
            else ((L,), cfg.d_ff_dense)
        stack.update(w_gate=norm((*lead, D, F), next(ks)),
                     w_up=norm((*lead, D, F), next(ks)),
                     w_down=norm((*lead, F, D), next(ks)))
        if sparse:
            stack["router"] = norm((L, D, cfg.n_experts), next(ks))
            if cfg.router_score == "sigmoid":
                stack["router_bias"] = norm((L, cfg.n_experts), next(ks))
        out[name] = stack
    return out


_MIXER2_AXES = {
    "norm": ("layers", "embed"),
    "in_proj": ("layers", "embed", "mlp"),
    "conv_w": ("layers", None, "mlp"),
    "conv_b": ("layers", "mlp"),
    "out_proj": ("layers", "mlp", "embed"),
    "dt_bias": ("layers", None), "A_log": ("layers", None),
    "D": ("layers", None), "w_norm": ("layers", "mlp")}


def _sala_stacks(cfg: LlamaConfig) -> Dict[str, Tuple[int, int, int, int]]:
    """name -> (layers, query heads, kv heads, head width) of each stack a
    model of linear and block-sparse layers has."""
    out = {"sparse": (cfg.kv_layers, cfg.n_heads, cfg.n_kv_heads,
                      cfg.head_dim),
           "linear": (cfg.state_layers, cfg.lightning_heads,
                      cfg.lightning_heads, cfg.lightning_head_dim)}
    return {k: v for k, v in out.items() if v[0]}


def _sala_gated(cfg: LlamaConfig, name: str) -> bool:
    return cfg.sparse_gate if name == "sparse" else cfg.lightning_gate


def _sala_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    out = {"embed": ("vocab", "embed"), "final_norm": ("embed",),
           "lm_head": ("embed", "vocab")}
    for name in _sala_stacks(cfg):
        stack = {"attn_norm": ("layers", "embed"),
                 "wq": ("layers", "embed", "heads"),
                 "wk": ("layers", "embed", "kv_heads"),
                 "wv": ("layers", "embed", "kv_heads"),
                 "wo": ("layers", "heads", "embed"),
                 "mlp_norm": ("layers", "embed"),
                 "w_gate": ("layers", "embed", "mlp"),
                 "w_up": ("layers", "embed", "mlp"),
                 "w_down": ("layers", "mlp", "embed")}
        if _sala_gated(cfg, name):
            stack["wg"] = ("layers", "embed", "heads")
        if name == "linear" and cfg.lightning_qk_norm:
            stack.update(q_norm=("layers", "head_dim"),
                         k_norm=("layers", "head_dim"))
        if name == "linear" and cfg.lightning_norm:
            stack["o_norm"] = ("layers", "heads")
        out[name] = stack
    return out


def _init_sala(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """A model of linear and block-sparse layers (`cfg.mixer_types`): a
    stack a kind (`_sala_stacks`), each with the block's projections at the
    kind's own heads, the gate's `wg` `[D, heads * head width]`, the dense
    feed-forward, and for the linear kind the norms of q and k a head and of
    the joined output. The embedding is drawn so that its rows times
    `embed_scale` start the stream at every other model's scale
    (`init_params` says why). Keys from lists of this function's own."""
    D, F, V, pd = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.param_dtype

    def norm(shape, k, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    top = iter(jax.random.split(key, 4))
    out = {"embed": norm((V, D), next(top), 0.02 / cfg.embed_scale),
           "final_norm": jnp.ones((D,), pd),
           "lm_head": norm((D, V), next(top))}
    for name, (L, H, KVH, hd) in _sala_stacks(cfg).items():
        ks = iter(jax.random.split(next(top), 8))
        stack = {"attn_norm": jnp.ones((L, D), pd),
                 "wq": norm((L, D, H * hd), next(ks)),
                 "wk": norm((L, D, KVH * hd), next(ks)),
                 "wv": norm((L, D, KVH * hd), next(ks)),
                 "wo": norm((L, H * hd, D), next(ks)),
                 "mlp_norm": jnp.ones((L, D), pd),
                 "w_gate": norm((L, D, F), next(ks)),
                 "w_up": norm((L, D, F), next(ks)),
                 "w_down": norm((L, F, D), next(ks))}
        gate_key = next(ks)
        if _sala_gated(cfg, name):
            stack["wg"] = norm((L, D, H * hd), gate_key)
        if name == "linear" and cfg.lightning_qk_norm:
            stack.update(q_norm=jnp.ones((L, hd), pd),
                         k_norm=jnp.ones((L, hd), pd))
        if name == "linear" and cfg.lightning_norm:
            stack["o_norm"] = jnp.ones((L, H * hd), pd)
        out[name] = stack
    return out


def _parts_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    into = ("mlp", "embed") if cfg.up_out_in else ("embed", "mlp")
    experts = {"mlp_norm": ("layers", "embed"),
               "router": ("layers", "embed", "expert"),
               "w_up": ("layers", "expert", *into),
               "w_down": ("layers", "expert", "mlp", "embed")}
    if cfg.ffn_gated:
        experts["w_gate"] = experts["w_up"]
    if cfg.router_score == "sigmoid":
        experts["router_bias"] = ("layers", "expert")
    if cfg.n_shared_experts:
        experts.update({"ws_up": ("layers", *into),
                        "ws_down": ("layers", "mlp", "embed")})
        if cfg.ffn_gated:
            experts["ws_gate"] = experts["ws_up"]
    out = {"embed": ("vocab", "embed"), "final_norm": ("embed",),
           "lm_head": ("embed", "vocab"),
           "layers": {"attn_norm": ("layers", "embed"),
                      "wq": ("layers", "embed", "heads"),
                      "wk": ("layers", "embed", "kv_heads"),
                      "wv": ("layers", "embed", "kv_heads"),
                      "wo": ("layers", "heads", "embed")},
           "mamba": dict(_MIXER2_AXES), "experts": experts}
    if cfg.tie_embeddings:
        del out["lm_head"]
    for name, n in _part_layers(cfg).items():
        if not n:       # a kind the pattern does not have has no stack
            del out[name]
    return out


def _part_layers(cfg: LlamaConfig) -> Dict[str, int]:
    """name -> layers of each stack a model of one-part layers has."""
    return {"layers": cfg.kv_layers, "mamba": cfg.state_layers,
            "experts": cfg.sparse_layers}


def _init_parts(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """A model of one-part layers (`cfg.layer_parts`): a stack a kind, each
    with its own part's leaves and a norm, nothing else: `layers` (attention:
    `attn_norm`, `wq`, `wk`, `wv`, `wo`), `mamba` (`_init_mamba`'s Mamba-2
    mixer, no feed-forward) and `experts` (`mlp_norm`, `router` over all
    n_experts, the experts HELD, `w_up` and `w_down` and, where the expert is
    gated, `w_gate`; the up and gate matrices `[d_ff, d_model]`, as `w_down`
    is, where the width is off the lanes, `cfg.up_out_in`; the sigmoid
    router's `router_bias`; the shared expert).
    A kind the pattern does not have has no stack. Keys from lists of this
    function's own."""
    D, H, KVH, V = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.vocab_size
    hd, F, pd = cfg.head_dim, cfg.d_ff, cfg.param_dtype

    def norm(shape, k, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    top = iter(jax.random.split(key, 5))
    out = {"embed": norm((V, D), next(top)), "final_norm": jnp.ones((D,), pd)}
    head_key = next(top)
    if not cfg.tie_embeddings:
        out["lm_head"] = norm((D, V), head_key)
    La, Lm, Le = cfg.kv_layers, cfg.state_layers, cfg.sparse_layers
    ks = iter(jax.random.split(next(top), 4))
    if La:
        out["layers"] = {"attn_norm": jnp.ones((La, D), pd),
                         "wq": norm((La, D, H * hd), next(ks)),
                         "wk": norm((La, D, KVH * hd), next(ks)),
                         "wv": norm((La, D, KVH * hd), next(ks)),
                         "wo": norm((La, H * hd, D), next(ks))}
    mixer_key = next(top)
    if Lm:
        out["mamba"] = _init_mamba(cfg, mixer_key, norm, ffn=False)
    if Le:
        ks = iter(jax.random.split(next(top), 8))
        up = (Le, cfg.n_held, F, D) if cfg.up_out_in \
            else (Le, cfg.n_held, D, F)
        experts = {"mlp_norm": jnp.ones((Le, D), pd),
                   "router": norm((Le, D, cfg.n_experts), next(ks)),
                   "w_up": norm(up, next(ks)),
                   "w_down": norm((Le, cfg.n_held, F, D), next(ks))}
        if cfg.ffn_gated:
            experts["w_gate"] = norm(up, next(ks))
        if cfg.router_score == "sigmoid":
            # (drawn as every other model's: `_init_latent` says why)
            experts["router_bias"] = norm((Le, cfg.n_experts), next(ks))
        if cfg.n_shared_experts:
            experts.update(_shared_expert(cfg, Le, ks, norm))
        out["experts"] = experts
    return out


def logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    if cfg.latent:
        return _latent_axes(cfg)
    if cfg.mixed:
        return _mixed_axes(cfg)
    if cfg.conv:
        return _conv_axes(cfg)
    if cfg.sala:
        return _sala_axes(cfg)
    if cfg.layer_parts is not None:
        return _parts_axes(cfg)
    layers: Dict[str, Tuple] = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if cfg.qk_norm:
        per_head = cfg.qk_norm == "head"
        layers.update({
            "q_norm": ("layers", "head_dim" if per_head else "heads"),
            "k_norm": ("layers", "head_dim" if per_head else "kv_heads")})
    if cfg.retention:
        layers.update({"wg": ("layers", "embed", None),
                       "bg": ("layers", None)})
    if cfg.index_topk:
        layers.update({"wiq": ("layers", "embed", None),
                       "wik": ("layers", "embed", None),
                       "wiw": ("layers", "embed", None),
                       "ik_norm": ("layers", None),
                       "ik_bias": ("layers", None)})
    if cfg.n_experts > 0:
        layers.update({
            "router": ("layers", "embed", "expert"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        })
    else:
        layers.update({
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    out = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    if cfg.tie_embeddings:
        del out["lm_head"]
    if cfg.n_shared_experts:
        layers.update({"ws_gate": ("layers", "embed", "mlp"),
                       "ws_up": ("layers", "embed", "mlp"),
                       "ws_down": ("layers", "mlp", "embed")})
    if cfg.ssm_state:
        mixer = {
            "norm": ("layers", "embed"),
            "in_proj": ("layers", "embed", "mlp"),
            "conv_w": ("layers", None, "mlp"),
            "conv_b": ("layers", "mlp"),
            "out_proj": ("layers", "mlp", "embed")}
        if cfg.ssm_heads:
            mixer = dict(_MIXER2_AXES)
        else:
            mixer.update({"x_proj": ("layers", "mlp", None),
                          "dt_norm": ("layers", None),
                          "b_norm": ("layers", None),
                          "c_norm": ("layers", None),
                          "dt_proj": ("layers", None, "mlp"),
                          "dt_bias": ("layers", "mlp"),
                          "A_log": ("layers", None, "mlp"),
                          "D": ("layers", "mlp")})
        out["mamba"] = dict(mixer, **{
            k: layers[k] for k in ("mlp_norm", "router", "w_gate", "w_up",
                                   "w_down", "ws_gate", "ws_up", "ws_down")
            if k in layers})
    return out


def _init_latent(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """A latent-attention model: `layers`, the sparse layers (all of them if
    the model is dense), and `dense`, the `first_dense` leading ones; both
    hold MLA's five matrices and two norms, neither a weight of a kind it is
    not. The experts' stacks hold the experts HELD (`cfg.experts_held`); the
    router scores them all. Keys are drawn from lists of this function's own,
    so no other model's weights move."""
    D, H, V, pd = cfg.d_model, cfg.n_heads, cfg.vocab_size, cfg.param_dtype
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim

    def norm(shape, k, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    def attention(L, ks):
        return {
            "attn_norm": jnp.ones((L, D), pd),
            "w_dq": norm((L, D, rq), next(ks)),
            "q_norm": jnp.ones((L, rq), pd),
            "w_uq": norm((L, rq, H * (dn + dr)), next(ks)),
            "w_dkv": norm((L, D, rkv + dr), next(ks)),
            "kv_norm": jnp.ones((L, rkv), pd),
            "w_ukv": norm((L, rkv, H * (dn + dv)), next(ks)),
            "wo": norm((L, H * dv, D), next(ks)),
            "mlp_norm": jnp.ones((L, D), pd),
        }

    def swiglu(lead, F, ks, prefix="w_"):
        return {prefix + "gate": norm((*lead, D, F), next(ks)),
                prefix + "up": norm((*lead, D, F), next(ks)),
                prefix + "down": norm((*lead, F, D), next(ks))}

    top = iter(jax.random.split(key, 4))
    out = {"embed": norm((V, D), next(top)),
           "final_norm": jnp.ones((D,), pd),
           "lm_head": norm((D, V), next(top))}
    Ls = cfg.n_layers - cfg.first_dense
    ks = iter(jax.random.split(next(top), 16))
    layers = attention(Ls, ks)
    if cfg.n_experts:
        layers["router"] = norm((Ls, D, cfg.n_experts), next(ks))
        layers.update(swiglu((Ls, cfg.n_held), cfg.d_ff, ks))
        if cfg.router_score == "sigmoid":
            # Published as zeros and moved by training UNTIL the load is
            # even; drawn here at the scale of every other leaf, so that it
            # decides some choices and the load stays near even.
            layers["router_bias"] = norm((Ls, cfg.n_experts), next(ks))
        if cfg.n_shared_experts:
            layers.update(swiglu((Ls,), cfg.n_shared_experts * cfg.d_ff, ks,
                                 "ws_"))
    else:
        layers.update(swiglu((Ls,), cfg.d_ff, ks))
    out["layers"] = layers
    if cfg.first_dense:
        ks = iter(jax.random.split(next(top), 16))
        out["dense"] = dict(attention(cfg.first_dense, ks),
                            **swiglu((cfg.first_dense,), cfg.d_ff_dense, ks))
    return out


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    if cfg.latent:
        return _init_latent(cfg, key)
    if cfg.mixed:
        return _init_mixed(cfg, key)
    if cfg.conv:
        return _init_conv(cfg, key)
    if cfg.sala:
        return _init_sala(cfg, key)
    if cfg.layer_parts is not None:
        return _init_parts(cfg, key)
    L, D, H, KVH = cfg.kv_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    if cfg.retention:       # `layers` is the whole stack, and pages nothing
        L = cfg.n_layers
    hd, F, V = cfg.head_dim, cfg.d_ff, cfg.vocab_size
    pd = cfg.param_dtype
    # One list of keys for every model: a leaf's key is its place in it, so
    # the indexer's leaves (the last) move no other model's weights, and the
    # state-space stack draws from a list of its own (`_init_mamba`).
    ks = iter(jax.random.split(key, 16))

    def norm(shape, k, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, D), pd),
        "wq": norm((L, D, H * hd), next(ks)),
        "wk": norm((L, D, KVH * hd), next(ks)),
        "wv": norm((L, D, KVH * hd), next(ks)),
        "wo": norm((L, H * hd, D), next(ks)),
        "mlp_norm": jnp.ones((L, D), pd),
    }
    if cfg.qk_norm:
        per_head = cfg.qk_norm == "head"
        layers.update({
            "q_norm": jnp.ones((L, hd if per_head else H * hd), pd),
            "k_norm": jnp.ones((L, hd if per_head else KVH * hd), pd)})
    if cfg.n_experts > 0:
        # The experts' stacks hold the experts HELD (`cfg.experts_held`: a
        # hybrid's share); the router scores them all.
        E, held = cfg.n_experts, cfg.n_held
        layers.update({
            "router": norm((L, D, E), next(ks)),
            "w_gate": norm((L, held, D, F), next(ks)),
            "w_up": norm((L, held, D, F), next(ks)),
            "w_down": norm((L, held, F, D), next(ks)),
        })
    else:
        layers.update({
            "w_gate": norm((L, D, F), next(ks)),
            "w_up": norm((L, D, F), next(ks)),
            "w_down": norm((L, F, D), next(ks)),
        })
    out = {
        # A model that multiplies its embedding's rows (`embed_scale`) is
        # drawn so that the PRODUCT starts the stream at the scale every
        # other model's does: at 0.02 itself, twelve times a row would drown
        # every branch, and under a tied head a token's own logit every
        # other's (the model would repeat its last token whatever the
        # layers computed, and no check could see them).
        "embed": norm((V, D), next(ks), 0.02 / cfg.embed_scale),
        "layers": layers,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": norm((D, V), next(ks)),
    }
    if cfg.tie_embeddings:
        del out["lm_head"]
    if cfg.ssm_state:
        out["mamba"] = _init_mamba(cfg, jax.random.fold_in(key, 1), norm)
    if cfg.index_topk:
        IH, Id = cfg.index_heads, cfg.index_head_dim
        layers.update({"wiq": norm((L, D, IH * Id), next(ks)),
                       "wik": norm((L, D, Id), next(ks)),
                       "wiw": norm((L, D, IH), next(ks)),
                       "ik_norm": jnp.ones((L, Id), pd),
                       "ik_bias": jnp.zeros((L, Id), pd)})
    if cfg.n_shared_experts:    # (a hybrid's: drawn last, as the indexer's)
        layers.update(_shared_expert(cfg, L, ks, norm))
    if cfg.retention:
        # The gate's constant: a kv head's half-life ln 2 / -logsigmoid(bg)
        # log-uniform in 16..4,096 positions (as `_init_mamba` draws
        # `dt_bias`), so that a state carries a prompt over hundreds of
        # steps; at 0 the gate is a half and a prompt is gone in ten. Keys of
        # its own: no other model's weights move.
        kg, kb = jax.random.split(jax.random.fold_in(key, 2))
        half = jnp.exp(jax.random.uniform(kb, (L, KVH), jnp.float32,
                                          math.log(16.0), math.log(4096.0)))
        keep = jnp.exp(-math.log(2.0) / half)       # the gate: sigmoid(bg)
        layers.update(wg=norm((L, D, KVH), kg),
                      bg=(jnp.log(keep) - jnp.log1p(-keep)).astype(
                          jnp.float32))
    return out


def _shared_expert(cfg: LlamaConfig, L: int, ks, norm) -> Dict[str, Any]:
    """The dense expert every token meets, `n_shared_experts * d_ff` wide
    (no `ws_gate` where the model's expert is not gated; `ws_up` and
    `ws_gate` laid as the routed experts' are, `cfg.up_out_in`)."""
    D, Fs = cfg.d_model, cfg.n_shared_experts * cfg.d_ff
    up = (L, Fs, D) if cfg.up_out_in else (L, D, Fs)
    out = {"ws_gate": norm(up, next(ks))} if cfg.ffn_gated else {}
    return dict(out, ws_up=norm(up, next(ks)),
                ws_down=norm((L, Fs, D), next(ks)))


def _init_mamba(cfg: LlamaConfig, key: jax.Array, norm, ffn: bool = True
                ) -> Dict[str, Any]:
    """The stack of state-space layers, each with its own feed-forward (a
    dense one, or a router, the experts held and the shared expert; none
    where `ffn` is False: a one-part layer is its mixer alone). The
    channel axis is the minor one of every leaf (`ops/ssm.py`): Mamba-1's
    `A_log` is `[N, Di]` and the convolution's weights `[K, Di]`. A and the
    time step's bias start as Mamba's own do (A = -(1..N), under Mamba-2 A =
    -uniform(1..16) a head; softplus(bias) log-uniform in 1e-3..1e-1), so
    that a state carries over hundreds of rows, not two."""
    Lm = cfg.state_layers
    D, F, Di, N = cfg.d_model, cfg.d_ff, cfg.ssm_inner, cfg.ssm_state
    K, R, pd = cfg.ssm_conv, cfg.ssm_dt_rank, cfg.param_dtype
    ks = iter(jax.random.split(key, 16))
    # a time step a head under Mamba-2, a channel under Mamba-1
    step = jnp.exp(jax.random.uniform(
        next(ks), (Lm, cfg.ssm_heads or Di), jnp.float32, jnp.log(1e-3),
        jnp.log(1e-1)))
    dt_bias = (step + jnp.log(-jnp.expm1(-step))).astype(pd)
    if cfg.ssm_heads:
        H, Dc = cfg.ssm_heads, cfg.ssm_conv_channels
        out = {
            "norm": jnp.ones((Lm, D), pd),
            # its columns: the gate z, then x, every group's B and every
            # group's C, then a head's dt
            "in_proj": norm((Lm, D, Di + Dc + H), next(ks)),
            "conv_w": norm((Lm, K, Dc), next(ks), K ** -0.5),
            "conv_b": norm((Lm, Dc), next(ks)),
            "dt_bias": dt_bias,
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (Lm, H), jnp.float32, 1.0, 16.0)).astype(pd),
            "D": jnp.ones((Lm, H), pd),
            "w_norm": jnp.ones((Lm, Di), pd),
            "out_proj": norm((Lm, Di, D), next(ks)),
        }
    else:
        out = {
            "norm": jnp.ones((Lm, D), pd),
            "in_proj": norm((Lm, D, 2 * Di), next(ks)),
            "conv_w": norm((Lm, K, Di), next(ks), K ** -0.5),
            "conv_b": norm((Lm, Di), next(ks)),
            "x_proj": norm((Lm, Di, R + 2 * N), next(ks)),
            "dt_norm": jnp.ones((Lm, R), pd),
            "b_norm": jnp.ones((Lm, N), pd),
            "c_norm": jnp.ones((Lm, N), pd),
            "dt_proj": norm((Lm, R, Di), next(ks)),
            "dt_bias": dt_bias,
            "A_log": (jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None]
                      + jax.random.normal(next(ks), (Lm, N, Di)) * 0.02
                      ).astype(pd),
            "D": jnp.ones((Lm, Di), pd),
            "out_proj": norm((Lm, Di, D), next(ks)),
        }
    if not ffn:
        return out
    out["mlp_norm"] = jnp.ones((Lm, D), pd)
    if cfg.n_experts:
        out["router"] = norm((Lm, D, cfg.n_experts), next(ks))
    lead = (Lm, cfg.n_held) if cfg.n_experts else (Lm,)
    out.update(w_gate=norm((*lead, D, F), next(ks)),
               w_up=norm((*lead, D, F), next(ks)),
               w_down=norm((*lead, F, D), next(ks)))
    if cfg.n_shared_experts:
        out.update(_shared_expert(cfg, Lm, ks, norm))
    return out


def param_count(cfg: LlamaConfig) -> int:
    """The model's weights. A retention layer's `bg` is a constant of the
    initialisation inside the gate's logsigmoid, no published weight, and is
    not counted."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    if cfg.retention:
        shapes = dict(shapes, layers={k: v for k, v in
                                      shapes["layers"].items() if k != "bg"})
    # (Python's integers: a stack of experts may pass 2^31 elements)
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _mesh_axes(spec_entry) -> Tuple[str, ...]:
    if spec_entry is None:
        return ()
    return (spec_entry,) if isinstance(spec_entry, str) else tuple(spec_entry)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array,
               ctx: Optional[ParallelContext]) -> jax.Array:
    """Causal flash attention on [B, H, S, hd] under the context's mesh.

    GSPMD cannot partition a Mosaic (Pallas TPU) kernel, and its
    lowering insists that EVERY mesh axis be manual around it. So where
    the kernel path is taken on a multi-device mesh (the XLA reference
    path partitions like any other op and keeps GSPMD's freedom to pad
    uneven batches), the call sits in a shard_map over the whole mesh:
    each device runs the kernel on its own [B/batch_axes, H/tp, S, hd]
    block, replicated over the remaining axes, and no collective is
    needed (attention never mixes batch rows or heads)."""
    if ctx is None or ctx.mesh.size == 1 or not pallas_eligible(q, k):
        return flash_attention(q, k, v, True)
    if jax.sharding.get_abstract_mesh().manual_axes:
        # Inside the pp shard_map the kernel would need a NESTED
        # shard_map over the remaining axes; jax 0.9 compiles its
        # forward but Shardy rejects the sharding of the residual it
        # saves for the backward ("manual axis after free axis").
        raise NotImplementedError(
            "the Pallas attention kernel cannot run under pipeline "
            "parallelism (pp > 1) on TPU yet: jax 0.9 cannot "
            "differentiate a shard_map nested in the pp shard_map. Use "
            "dp/fsdp/tp (or sp ring attention) without pp.")
    batch_axes = _mesh_axes(ctx.rules["batch"])
    head_axes = _mesh_axes(ctx.rules["heads"])
    spec = P(batch_axes or None, head_axes or None, None, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, True), mesh=ctx.mesh,
        in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)


def _layer_fwd(lp: Dict[str, jax.Array], x: jax.Array, cos, sin, positions,
               cfg: LlamaConfig, sp_manual: bool,
               ctx: Optional[ParallelContext] = None,
               index_tables=None) -> jax.Array:
    B, S, _ = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype

    # The block's two cache-free halves are models/block.py's, shared with
    # serve/engine.py; scope names are the vocabulary a device trace is
    # reduced by (benchmark/program_trace.py).
    q, k, v, *index = attention_inputs(
        lp, x, cfg,
        (lambda t: apply_rope(t, cos, sin, positions)) if cfg.rope
        else (lambda t: t),
        (lambda t: apply_rope(t, *index_tables)) if index_tables else None)
    with jax.named_scope("attn"):
        if index:
            # Attention over the indexer's selection: K and V by kv head, the
            # selection the same for every head.
            qi, ki, w = index[0]
            attn = sparse_attention(q, k, v, qi.transpose(0, 2, 1, 3),
                                    ki[:, 0], w, cfg.index_topk)
        elif sp_manual:
            attn = ring_attention(q, repeat_kv(k, H // KVH),
                                  repeat_kv(v, H // KVH), axis_name="sp",
                                  causal=True)
        else:
            attn = _attention(q, repeat_kv(k, H // KVH),
                              repeat_kv(v, H // KVH), ctx)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    with jax.named_scope("attn_out"):
        x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))

    x, sparse = feed_forward(lp, x, cfg)
    aux = sparse[0] if sparse else jnp.zeros((), jnp.float32)
    return x, aux


def _stack_fwd(layers_p: Dict[str, Any], x: jax.Array, cos, sin,
               cfg: LlamaConfig, sp_manual: bool,
               ctx: Optional[ParallelContext] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Scan over a stack of layers (leading 'layers' axis on every leaf).

    Returns (x, summed MoE aux loss across the stack)."""
    if sp_manual:
        offset = jax.lax.axis_index("sp") * x.shape[1]
    else:
        offset = 0
    positions = offset + jnp.arange(x.shape[1])
    index_tables = None
    if cfg.index_topk:
        if sp_manual or (ctx is not None and ctx.mesh.size > 1):
            raise NotImplementedError(
                "sparse attention (index_topk > 0) runs on one device: its "
                "selection is over the whole sequence and is not sharded")
        # The indexer's own rotary tables, over its own width, at the text
        # stream's positions.
        icos, isin = rope_frequencies(cfg.index_head_dim, cfg.max_seq,
                                      cfg.rope_theta)
        index_tables = (icos, isin, positions)
    if cfg.mrope_section:
        # Three position streams; tokens alone set them equal (a vision
        # tower would hand its own), and the tables are then the rows
        # `positions` names: from here on a token's row is its index.
        cos, sin = mrope_tables(
            cos, sin, jnp.broadcast_to(positions, (3,) + positions.shape),
            cfg.mrope_section)
        positions = None

    def body(carry, lp):
        x, aux_sum = carry
        x, aux = _layer_fwd(lp, x, cos, sin, positions, cfg, sp_manual,
                            ctx, index_tables)
        return (x, aux_sum + aux), None

    if cfg.remat:
        policies = {
            "full": None,
            "none": jax.checkpoint_policies.everything_saveable,
            "dots": jax.checkpoint_policies.dots_saveable,
            "dots_nobatch":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        }
        if cfg.remat_policy not in policies:
            raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}; "
                             f"one of {sorted(policies)}")
        policy = policies[cfg.remat_policy]
        body = jax.checkpoint(body, policy=policy) if policy is not None \
            else jax.checkpoint(body)
    aux0 = (x[(0,) * x.ndim] * 0).astype(jnp.float32)  # inherits x's vma type
    with jax.named_scope("layers"):
        (x, aux), _ = jax.lax.scan(body, (x, aux0), layers_p)
    return x, aux


def forward_with_aux(params: Dict[str, Any], tokens: jax.Array,
                     cfg: LlamaConfig,
                     ctx: Optional[ParallelContext] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, S] -> (logits [B, S, V] float32, MoE aux loss scalar)."""
    if cfg.ssm_state:
        raise NotImplementedError(
            "state-space layers (ssm_state > 0) run through Serve only: the "
            "training forward has no hybrid stack and `ops.ssm`'s kernel no "
            "backward (ROADMAP, Reach)")
    if cfg.retention:
        raise NotImplementedError(
            "power retention (mixer 'retention') runs through Serve only: "
            "the training forward has no retention layer and "
            "`ops.retention`'s kernels no backward (ROADMAP, Reach)")
    if cfg.latent:
        raise NotImplementedError(
            "latent attention (kv_lora_rank > 0) runs through Serve only: the "
            "training forward has no stack of dense-then-sparse segments, "
            "and a share of the experts (experts_held) takes no gradient "
            "for the experts that are absent (ROADMAP, Reach)")
    if cfg.mixed:
        raise NotImplementedError(
            "mixed attention (attn_pattern: window beside full attention) "
            "runs through Serve only: the training forward has no stack of "
            "segments by kind, the window and the sink have no backward "
            "kernel, and a share of the experts (experts_held) takes no "
            "gradient for the experts that are absent (ROADMAP, Reach)")
    if cfg.conv:
        raise NotImplementedError(
            "short-convolution layers (conv_layers) run through Serve only: "
            "the training forward has no stack of segments by kind, and no "
            "flash kernel here has a backward at a head of half a tile "
            "(ROADMAP, Reach)")
    if cfg.sala:
        raise NotImplementedError(
            "linear and block-sparse layers (mixer_types) run through Serve "
            "only: the training forward has no stack of segments by kind, "
            "and `ops.linear_attention`'s and the block mask's kernels no "
            "backward (ROADMAP, Reach)")
    if cfg.multipliers:
        raise NotImplementedError(
            "embed_scale, residual_scale, logit_scale and attn_scale run "
            "through Serve only: the training forward's attention paths "
            "(flash under a mesh, ring) take no scale of the model's "
            "(ROADMAP, Reach)")
    if cfg.block_length > 1:
        raise NotImplementedError(
            "generation by blocks (block_length > 1) runs through Serve "
            "only: the training forward's attention paths are causal, its "
            "loss is the next token's, and the flash backward kernels have "
            "no block mask (ROADMAP, Reach)")
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    with jax.named_scope("rope"):
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq,
                                    cfg.rope_theta)

    sp = ctx.sp if ctx else 1
    pp = ctx.pp if ctx else 1
    sp_manual = sp > 1

    if ctx is not None:
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(ctx.mesh, ctx.activation_spec()))

    if pp > 1:
        # Reshape stacked layers [L, ...] -> [pp, L/pp, ...] and microbatch.
        from ray_tpu.parallel.pipeline import gpipe_spmd
        L = cfg.n_layers
        assert L % pp == 0, (L, pp)
        stage_layers = jax.tree.map(
            lambda p: p.reshape(pp, L // pp, *p.shape[1:]), params["layers"])
        M = cfg.num_microbatches or pp
        B = x.shape[0]
        assert B % M == 0, (B, M)
        x_mb = x.reshape(M, B // M, *x.shape[1:])

        stage_fn = functools.partial(_stack_fwd, cos=cos, sin=sin, cfg=cfg,
                                     sp_manual=sp_manual, ctx=ctx)
        manual = {"pp"} | ({"sp"} if sp_manual else set())
        param_spec = jax.tree.map(lambda _: P("pp"), stage_layers)
        mb_spec = P(None, None, "sp", None) if sp_manual else P()
        def _pipe_body(sp_params, mb):
            out, aux = gpipe_spmd(stage_fn, sp_params, mb,
                                  axis_name="pp", with_aux=True)
            if sp_manual:
                aux = jax.lax.pmean(aux, "sp")
            return out, aux

        aux_spec = P()
        pipe = jax.shard_map(
            _pipe_body,
            mesh=ctx.mesh, in_specs=(param_spec, mb_spec),
            out_specs=(mb_spec, aux_spec), axis_names=manual)
        x, aux = pipe(stage_layers, x_mb)
        x = x.reshape(B, *x.shape[2:])
    elif sp_manual:
        def _stack_pmean_aux(lp, xx):
            y, aux = _stack_fwd(lp, xx, cos, sin, cfg, True)
            return y, jax.lax.pmean(aux, "sp")

        stack = jax.shard_map(
            _stack_pmean_aux,
            mesh=ctx.mesh,
            in_specs=(jax.tree.map(lambda _: P(), params["layers"]),
                      P(None, "sp", None)),
            out_specs=(P(None, "sp", None), P()),
            axis_names={"sp"})
        x, aux = stack(params["layers"], x)
    else:
        x, aux = _stack_fwd(params["layers"], x, cos, sin, cfg, False, ctx)

    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(dt))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                params["lm_head"].astype(dt))
        return logits.astype(jnp.float32), aux


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
            ctx: Optional[ParallelContext] = None) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] (float32)."""
    return forward_with_aux(params, tokens, cfg, ctx)[0]


def loss_fn(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
            ctx: Optional[ParallelContext] = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy (+ weighted MoE aux loss); targets = tokens
    shifted left, last position masked."""
    logits, aux = forward_with_aux(params, tokens, cfg, ctx)
    with jax.named_scope("loss"):
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        mask = jnp.concatenate(
            [jnp.ones(tokens[:, 1:].shape, jnp.float32),
             jnp.zeros(tokens[:, :1].shape, jnp.float32)], axis=1)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1)[..., 0]
        ce = (logz - gold) * mask
        loss = jnp.sum(ce) / jnp.maximum(jnp.sum(mask), 1.0)
        if cfg.n_experts > 0:
            loss = loss + cfg.moe_aux_weight * aux
        return loss, {"loss": loss, "tokens": jnp.sum(mask)}
