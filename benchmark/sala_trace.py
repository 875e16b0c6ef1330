"""Device self-time by scope for the scopes of a stack of linear and
block-sparse layers (`arch: minicpm_sala`).

`program_trace.py` reduces a trace by a fixed vocabulary of scope names, in
which `linear_attn` (with `linear_chunk` and `linear_step` inside), `compress`,
`block_select`, `block_sparse_attn` and `attn_gate`
(ray_tpu/models/block.py::linear_mixer, ray_tpu/models/serving.py::
_block_sparse_kind, ray_tpu/ops/sparse_attention.py) do not appear: an
instruction under `attn/linear_attn` is charged to `attn` there, which keeps
the outer names their meaning. The readers of this stack's metrics need the
deeper names. `retention_trace.py`'s reduction with these names in its
vocabulary's place for the length of a call: same trace, same events, same
rule (an instruction's time less its children's, charged to the deepest scope
of its path that is in the vocabulary). A program without these scopes gives
None, and every reader over this file then returns None.

    python3 benchmark/sala_trace.py benchmark/out/<cell>/<seed>/trace

prints, for `jit_prefill` and `jit_decode`, the mean device self-time an
execution by scope under this vocabulary.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Optional, Sequence
from unittest import mock

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import moe_trace, retention_trace  # noqa: E402

LINEAR = ("linear_attn", "linear_chunk", "linear_step")
SELECT = ("compress", "block_select")
SPARSE = ("block_sparse_attn",)
SCOPES = LINEAR + SELECT + SPARSE + ("attn_gate",)
VOCABULARY = moe_trace.VOCABULARY + SCOPES
BYTES = retention_trace.BYTES
device_peaks = retention_trace.device_peaks
span_median = retention_trace.span_median


def _under():
    return mock.patch.multiple(retention_trace, VOCABULARY=VOCABULARY,
                               SCOPES=SCOPES)


def prefills(run: dict):
    """`retention_trace.prefills` under this vocabulary."""
    with _under():
        return retention_trace.prefills(run)


def decodes(run: dict):
    """`retention_trace.decodes` under this vocabulary."""
    with _under():
        return retention_trace.decodes(run)


def ns(per_scope: Dict[str, float], scopes: Sequence[str]) -> float:
    return sum(per_scope.get(s, 0.0) for s in scopes)


def counts_of(run: dict) -> Optional[object]:
    """The adapter's counts where they count this stack; else None."""
    from benchmark import models
    counts = models.adapter(run["config"]["arch"]).counts
    return counts if hasattr(counts, "linear_step_ops_bytes") else None


def prefill_ms_per_ktok(run: dict, scopes: Sequence[str]) -> Optional[float]:
    """Device self-time under `scopes` in the `jit_prefill` executions of the
    trace over the thousands of prompt tokens of the admits paired with
    them."""
    pairs = prefills(run)
    if not pairs:
        return None
    tokens = sum(admit.args["prompt_tokens"] for admit, _, _ in pairs)
    if not tokens:
        return None
    return sum(ns(d, scopes) for _, _, d in pairs) / 1e6 / (tokens / 1e3)


def decode_step_ms(run: dict, scopes: Sequence[str]) -> Optional[float]:
    """Device self-time a decode step under `scopes` in `jit_decode`, the
    median over the whole executions of the trace."""
    from benchmark.stats import median
    dec = decodes(run)
    if dec is None:
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return median([ns(d, scopes) for d in dec[1]]) / 1e6 / chunk


if __name__ == "__main__":
    with _under():
        sys.exit(retention_trace.main(sys.argv))
