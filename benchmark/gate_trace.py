"""Device self-time under the scope `attn_gate` (ray_tpu/models/block.py::
gated: a mixed-attention layer's output times the sigmoid of its gate's logit
a head, before `wo`), for the two readers of the gate's metrics.

`window_trace.py` reduces a trace by a fixed vocabulary of scope names in
which `attn_gate` does not appear: what runs under it is charged there to
`attn`, the scope around it. The same reduction with that one name more, the
vocabulary put in `window_trace`'s place for the length of a call: same
trace, same events, same rule. A trace without a mixed stack's scopes gives
None; a mixed stack's program in which nothing ran under the gate's scope
(a model without one, or a compiler that made the product part of another
scope's operation) reads 0.
"""

from __future__ import annotations

from typing import Optional, Tuple
from unittest import mock

from benchmark import window_trace

SCOPE = "attn_gate"
VOCABULARY = window_trace.VOCABULARY + (SCOPE,)


def prefill(run: dict) -> Optional[Tuple[list, float]]:
    """`window_trace.prefill_scope` of the gate's scope."""
    with mock.patch.object(window_trace, "VOCABULARY", VOCABULARY):
        return window_trace.prefill_scope(run, [SCOPE])


def decode(run: dict) -> Optional[Tuple[float, float]]:
    """`window_trace.decode_scope` of the gate's scope."""
    with mock.patch.object(window_trace, "VOCABULARY", VOCABULARY):
        return window_trace.decode_scope(run, [SCOPE], "active")
