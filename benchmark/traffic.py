"""The one traffic generator: a mix is a data file, never code.

`benchmark/traffic/<name>.json` holds the parameters of a mix. Two kinds of
mix exist, and a later cell picks one by the file's `kind`:

  requests (kinds `serve_open`, `serve_closed`): prompt and output lengths
    from a named distribution, arrivals from a named process or a number of
    closed-loop clients;
  rows (kind `train`): fixed-length rows of token ids.

Seeding. The SIZES of a run (its multiset of lengths and of gaps between
arrivals) are drawn from the mix's own `shape_seed`, so every `--seed` does
the same work; `--seed` decides their ORDER (open loop; a closed loop keeps
the order too, because it gets through only the head of its pool) and every
token id. Runs with different seeds then differ by ordering alone, which is
what lets the spread between seeds be read as noise (arrival sampling adapted from
ray_tpu/load/arrivals.py, which draws sizes and gaps from the run's seed).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    t_sched: float        # seconds from the window's start (0 in a closed loop)
    prompt: List[int]
    max_tokens: int


def _draw(dist: Dict[str, Any], n: int, rng: np.random.Generator) -> np.ndarray:
    """n integer lengths from a named distribution."""
    kind = dist["dist"]
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "fixed":
        return np.full(n, lo, np.int64)
    if kind == "uniform":
        return rng.integers(lo, hi + 1, n)
    if kind == "bounded_pareto":
        # Inverse CDF of a Pareto(alpha) truncated to [lo, hi].
        a = float(dist["alpha"])
        u = rng.random(n)
        x = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
        return np.minimum(np.floor(x).astype(np.int64), hi)
    raise ValueError(f"unknown length distribution {kind!r}")


def _scaled(dist: Dict[str, Any], scale: float) -> Dict[str, Any]:
    out = dict(dist)
    out["min"] = max(1, int(dist["min"] * scale))
    out["max"] = max(out["min"], int(dist["max"] * scale))
    return out


def request_shapes(mix: Dict[str, Any], seconds: float, scale: float = 1.0):
    """(gaps, prompt_lens, output_lens) of one window, from `shape_seed`
    alone. Open loop: as many as arrive inside `seconds`. Closed loop: a pool
    of `pool_per_client_second * clients * seconds` requests, more than the
    clients can finish."""
    rng = np.random.default_rng(int(mix["shape_seed"]))
    arr = mix["arrivals"]
    if arr["process"] == "poisson":
        rate = float(arr["rate_per_s"])
        # A fixed over-draw, then the prefix that fits: the same prefix for
        # every seed because it never depends on the run's seed.
        gaps = rng.exponential(1.0 / rate, int(rate * seconds * 2) + 16)
        n = int(np.searchsorted(np.cumsum(gaps), seconds))
        gaps = gaps[:n]
    elif arr["process"] == "closed":
        n = int(np.ceil(arr["clients"] * seconds
                        * float(arr["pool_per_client_second"])))
        gaps = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    prompts = _draw(_scaled(mix["prompt_tokens"], scale), n, rng)
    outputs = _draw(_scaled(mix["output_tokens"], scale), n, rng)
    return gaps, prompts, outputs


def requests(mix: Dict[str, Any], seconds: float, seed: int, vocab: int,
             scale: float = 1.0) -> List[Request]:
    """The window's requests in the order they are offered."""
    gaps, prompts, outputs = request_shapes(mix, seconds, scale)
    rng = np.random.default_rng(int(seed))
    gaps = rng.permutation(gaps)
    # A closed loop gets through only the head of its pool, so another order
    # would be another subset, which is other work: its order is fixed too,
    # and the seed decides the token ids alone.
    order = (np.arange(len(prompts)) if mix["arrivals"]["process"] == "closed"
             else rng.permutation(len(prompts)))
    times = np.cumsum(gaps)
    out = []
    for i, j in enumerate(order):
        ids = rng.integers(1, vocab, int(prompts[j]))
        out.append(Request(i, float(times[i]), [int(t) for t in ids],
                           int(outputs[j])))
    return out


def sample_prompts(lengths: List[int], seed: int, vocab: int) -> List[List[int]]:
    """Prompts of the given lengths for the correctness check."""
    rng = np.random.default_rng([int(seed), 0x5eed])
    return [[int(t) for t in rng.integers(1, vocab, n)] for n in lengths]


def rows(mix: Dict[str, Any], n_rows: int, seed: int, vocab: int,
         scale: float = 1.0) -> np.ndarray:
    """[n_rows, seq] int32 token ids for a train mix."""
    seq = max(8, int(mix["seq_tokens"] * scale))
    rng = np.random.default_rng(int(seed))
    return rng.integers(0, vocab, (n_rows, seq), dtype=np.int32)
