"""Scheduler (serve/engine.py admission + prefill): 95th percentile of call
entry -> first token, both stamped inside the replica around the inherited
`LLMServer.__call__`. program_span."""

from benchmark.stats import percentile


def read(run):
    vals = [s[1] - s[0] for s in run["replica"]["stamps"].values()]
    p = percentile(vals, 95.0)
    return None if p is None else p * 1e3
