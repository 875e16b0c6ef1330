"""Normalization and rotary-embedding ops (pure jnp — XLA fuses these into
adjacent matmuls on TPU; a Pallas version is only warranted if profiles show
fusion misses)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [max_seq, head_dim//2]
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_inv_frequencies(dim: int, theta: float, factor: float,
                         original_max: int, beta_fast: float,
                         beta_slow: float) -> jax.Array:
    """YaRN's `dim // 2` rotary frequencies: pair i turns by
    f_i = theta^(-2i/dim) where it turns fast (extrapolated as trained), by
    f_i / factor where it turns slowly (interpolated), and by a linear ramp
    between the two from pair `lo` to pair `hi`, the pairs that make
    `beta_fast` and `beta_slow` turns over the `original_max` positions of
    training: floor and ceil of dim ln(original_max / (b 2 pi)) / (2 ln
    theta), clipped to [0, dim - 1]."""
    import math
    f = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))

    def pair(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair(beta_fast)), 0)
    hi = min(math.ceil(pair(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def yarn_frequencies(dim: int, max_seq: int, theta: float, factor: float,
                     original_max: int, beta_fast: float, beta_slow: float):
    """`rope_frequencies` at YaRN's frequencies: (cos, sin) [max_seq, dim//2].
    The tables carry no magnitude factor: it is 1 where `mscale` equals
    `mscale_all_dim`, and YaRN's correction then lies on the softmax scale
    alone (`LlamaConfig.softmax_scale`)."""
    inv_freq = yarn_inv_frequencies(dim, theta, factor, original_max,
                                    beta_fast, beta_slow)
    freqs = jnp.outer(jnp.arange(max_seq, dtype=jnp.float32), inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: jax.Array | None = None) -> jax.Array:
    """x: [b, h, s, d]; cos/sin: [max_seq, d//2]; positions: [s] global positions."""
    s = x.shape[2]
    if positions is None:
        positions = jnp.arange(s)
    c = cos[positions][None, None]  # [1,1,s,d//2]
    si = sin[positions][None, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * si, x1 * si + x2 * c], axis=-1)
    return out.astype(x.dtype)


def apply_rope_narrow(x: jax.Array, cos: jax.Array, sin: jax.Array
                      ) -> jax.Array:
    """`apply_rope` at positions 0..s-1 for a rotary part NARROWER than a
    tile's 128 lanes (x `[b, h, s, d]`, d = 64 at MiMo-V2's widths; cos/sin
    `[s, d//2]`): `apply_rope`'s own products and sums and no other rounding,
    so to the bit its result op by op, and under jit in bfloat16
    (tests/test_mimo.py holds both). Cutting a 64-wide minor
    axis into halves of 32 and joining them again re-lays every vector (1.4
    ms a layer for q and k of a prompt of 8,192 on a v5e; PERF.md, PR 42;
    PR 39 read 4.6 ms a prefill the same way), so the halves are swapped,
    with the sign, by one small matmul against a signed permutation instead
    (each output is ONE input times +-1: exact in any dtype), and the tables
    are laid side by side: out = x * [cos ; cos] + (x P) * [sin ; sin]."""
    d = x.shape[-1]
    half = d // 2
    i = jnp.arange(d)
    # (x P)[j] = -x[j + half] for j < half, x[j - half] for j >= half
    swap = (jnp.where(i[:, None] == (i[None, :] + half) % d, 1.0, 0.0)
            * jnp.where(i[None, :] < half, -1.0, 1.0)).astype(x.dtype)
    turned = jnp.einsum("bhsd,de->bhse", x, swap,
                        precision=jax.lax.Precision.HIGHEST)
    c = jnp.concatenate([cos, cos], axis=-1)[None, None, :x.shape[2]]
    si = jnp.concatenate([sin, sin], axis=-1)[None, None, :x.shape[2]]
    out = x.astype(jnp.float32) * c + turned.astype(jnp.float32) * si
    return out.astype(x.dtype)


def mrope_tables(cos: jax.Array, sin: jax.Array, positions: jax.Array,
                 sections) -> tuple:
    """Multimodal RoPE (Qwen2-VL's `mrope_section`): three position streams
    (temporal, height, width), `positions` [3, s], and the rotary
    frequencies cut into `sections` (summing to d//2) of which section j
    turns by stream j. cos/sin: [max_seq, d//2] -> (cos, sin) [s, d//2], one
    row a token, which `apply_rope` takes with `positions=None`. Text gives
    the three streams equal, and the rows are then `cos[positions[0]]`: the
    RoPE above."""
    stream = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                        total_repeat_length=cos.shape[-1])       # [d//2]
    mine = stream[None, :] == jnp.arange(len(sections))[:, None]  # [3, d//2]

    def pick(table):
        return jnp.sum(jnp.where(mine[:, None, :], table[positions], 0.0),
                       axis=0)

    return pick(cos), pick(sin)
