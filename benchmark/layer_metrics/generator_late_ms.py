"""Load generator: 95th percentile of (send time - scheduled time). A starved
generator must not be read as a fast server. host_clock."""

from benchmark.stats import percentile


def read(run):
    late = [o["t_send"] - o["t_sched"] for o in run["outcomes"] if o["t_send"]]
    p = percentile(late, 95.0)
    return None if p is None else p * 1e3
