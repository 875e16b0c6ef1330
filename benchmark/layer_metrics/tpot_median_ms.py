"""Median over requests of (last byte - first byte) / (output tokens - 1): the
steadier statistic beside `tpot_p95_ms`. The tail is a handful of short
requests whose few decode chunks shared the chip with a prefill or two, and
moves with the order of arrivals; the median is the decode chunk plus the
average prefill stall, and does not. Same counting as the tail: a failed
request counts with the window's length. host_clock."""

from benchmark.stats import median


def read(run):
    vals = []
    for o in run["outcomes"]:
        if o["abandoned"]:
            continue
        if not o["ok"]:
            vals.append(run["seconds"])
        elif o["tokens"] > 1:
            vals.append((o["t_last"] - o["t_first"]) / (o["tokens"] - 1))
    p = median(vals)
    return None if p is None else p * 1e3
