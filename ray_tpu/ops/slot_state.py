"""The recurrent state of a model with state-space layers, on the device:
its layout, and the only ops on it. The sibling of `ops/paged_kv.py` for
what has no pages: a state-space layer keeps, for every slot, a state of
FIXED size whatever the sequence's length, so there is nothing to page and
no block table; a slot's row is simply the slot's index.

The state is a pair of arrays, for the model's `n_layers` state-space layers
(ordinals into its `mamba` stack) and the engine's `n_slots`:

  ssm   [n_layers, n_slots, N, Di]      float32: `ops.ssm`'s state
  conv  [n_layers, K - 1, n_slots, Di]  the convolution's window, the last
                                        K - 1 inputs of each channel

The channel axis is the minor one of both and the axis before it a whole
tile (N = 16 rows of float32; 16 slots of bfloat16), so neither is padded:
with N as the minor axis the first would take eight times its size.

  * ``empty_state`` makes it; ``write_state`` puts a prefill's final state
    and window into ONE slot's rows, all layers at once, overwriting the
    whole of what the slot's previous tenant left; ``layer_state`` and
    ``update_layer`` are a decode step's read and write of one layer, the
    write only where a slot is active, so an idle slot's state never moves.
  * Like the arena, the state rides the decode program's loop CARRY and is
    donated: `update_layer` is an in-place write of one layer's rows.
    Nothing outside this module indexes it.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

State = Tuple[jax.Array, jax.Array]


def empty_state(n_layers: int, n_slots: int, n_state: int, channels: int,
                conv: int, dtype) -> State:
    """-> (ssm, conv), zeroed."""
    return (jnp.zeros((n_layers, n_slots, n_state, channels), jnp.float32),
            jnp.zeros((n_layers, conv - 1, n_slots, channels), dtype))


def state_bytes(state: State) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in state)


def write_state(state: State, slot, ssm_rows, conv_rows) -> State:
    """A prefill's result into slot `slot` (a traced scalar): `ssm_rows`
    `[n_layers, N, Di]`, `conv_rows` `[n_layers, K - 1, Di]`."""
    ssm, conv = state
    with jax.named_scope("state_write"):
        return (ssm.at[:, slot].set(ssm_rows.astype(ssm.dtype)),
                conv.at[:, :, slot].set(conv_rows.astype(conv.dtype)))


def layer_state(state: State, layer) -> State:
    """Layer `layer`'s (ssm `[n_slots, N, Di]`, conv `[K - 1, n_slots,
    Di]`)."""
    return state[0][layer], state[1][layer]


def update_layer(state: State, layer, active, ssm_rows, conv_rows) -> State:
    """A decode step's new state of one layer, kept only for the slots
    `active` `[n_slots]` marks: the others' rows stay what they were."""
    ssm, conv = state
    ssm_rows = jnp.where(active[:, None, None], ssm_rows, ssm[layer])
    conv_rows = jnp.where(active[None, :, None],
                          conv_rows.astype(conv.dtype), conv[layer])
    return ssm.at[layer].set(ssm_rows), conv.at[layer].set(conv_rows)
