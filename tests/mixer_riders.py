"""What tests/test_jamba.py, test_granite.py and test_nemotron_h.py hold a
state-space mixer's prompt pass WITH RIDERS to (`models/block.py`, PR 58),
and tests/test_lfm2.py the short-convolution operator's, whose slots keep a
window and no recurrent state (PR 60):
the bucket's last `n_slots` rows are one token a slot, and the pass (a)
leaves the prompt's output rows, kept state and window to the bit what the
pass without riders leaves, and (b) gives each riding slot the output row,
state and window that `step=True` gives it alone, an idle slot's state and
every other layer's untouched. Float32, op by op (no jit: the same ops on
the same values are the same bits)."""

import numpy as np

import jax
import jax.numpy as jnp

from ray_tpu.ops import slot_state

SLOTS, LAYERS, LAYER = 4, 3, 1
BUCKET, LENGTH = 48, 30
ACTIVE = np.array([True, False, True, True])


def _same(got, want):
    return (np.asarray(got) == np.asarray(want)).all()


def check(mixer, lp, cfg, step, tol, empty=None):
    """`mixer` is `mamba_mixer`, `mamba2_mixer` or `conv_mixer`; `step(x [ns,
    D], slots, layer, active) -> (out [ns, D], slots)` is its `step=True`
    alone on the slots' whole state, the window's update included; `empty`
    the slots' state zeroed (None: a state-space model's, of `cfg`'s sizes;
    `conv_mixer`'s has no recurrent part, `ops/slot_state.py`)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 4))
    x = jax.random.normal(next(keys), (BUCKET, cfg.d_model))
    if empty is None:
        empty = slot_state.empty_state(
            LAYERS, SLOTS, cfg.ssm_state, cfg.ssm_inner, cfg.ssm_conv,
            jnp.float32, cfg.ssm_conv_channels)
    slots = tuple(None if a is None
                  else jax.random.normal(next(keys), a.shape) for a in empty)
    active = jnp.asarray(ACTIVE)

    plain = mixer(lp, x, cfg, length=LENGTH)
    *riding, rode = mixer(lp, x, cfg, length=LENGTH, riders=slots,
                          layer=LAYER, active=active)
    # (a) the prompt's rows, state and window: to the bit
    assert _same(riding[0][:LENGTH], plain[0][:LENGTH])
    assert len(riding) == len(plain)
    assert all(_same(got, kept) for got, kept in zip(riding[1:], plain[1:]))
    # (b) a riding slot's row and state: the step's alone
    out, want = step(x[-SLOTS:], slots, LAYER, active)
    err = np.abs(np.asarray(riding[0][-SLOTS:]) - np.asarray(out))[ACTIVE]
    assert err.max() < tol, err.max()
    # ... and not the prompt pass's own rows there (the test would pass on
    # a mixer that ignored its riders otherwise)
    assert np.abs(np.asarray(plain[0][-SLOTS:])
                  - np.asarray(out))[ACTIVE].min(axis=0).max() > 100 * tol
    # the slots' axis in a layer's rows: 0 of the state, 1 of the window
    for axis, got, exp, was in zip((0, 1), rode, want, slots):
        if was is None:     # no recurrent part: nothing handed back for one
            assert got is None and exp is None
            continue
        got, exp, was = (np.asarray(a) for a in (got, exp, was))
        assert np.abs(got - exp).max() < tol
        others = [l for l in range(LAYERS) if l != LAYER]
        assert _same(got[others], was[others])
        assert _same(np.compress(~ACTIVE, got[LAYER], axis),
                     np.compress(~ACTIVE, was[LAYER], axis))
        assert not _same(np.compress(ACTIVE, got[LAYER], axis),
                         np.compress(ACTIVE, was[LAYER], axis))
