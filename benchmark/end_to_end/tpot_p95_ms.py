"""95th percentile over requests of (last byte - first byte) / (output tokens
- 1). Per request and not per gap, because the engine emits `decode_chunk`
tokens at once. A failed request counts with the window's length.
host_clock."""

from benchmark.stats import percentile


def read(run):
    vals = []
    for o in run["outcomes"]:
        if o["abandoned"]:
            continue
        if not o["ok"]:
            vals.append(run["seconds"])
        elif o["tokens"] > 1:
            vals.append((o["t_last"] - o["t_first"]) / (o["tokens"] - 1))
    p = percentile(vals, 95.0)
    return None if p is None else p * 1e3
