"""Small statistics shared by the metric readers."""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def train_rate(run) -> Optional[float]:
    """Tokens a second a chip over the window: from its opening to the end of
    the step in flight when `--seconds` ran out."""
    steps = run["steps"]
    if not steps:
        return None
    window = steps[-1]["t_done"] - run["t0"]
    return len(steps) * run["tokens_per_step"] / window / run["chips"]
