"""95th percentile, over every request offered in the window, of the time from
the request's SCHEDULED arrival to the first streamed byte at the HTTP client.
A failed or timed-out request counts with the window's length. host_clock."""

from benchmark.stats import percentile


def read(run):
    miss = run["seconds"]
    lat = [(o["t_first"] - o["t_sched"]) if o["ok"] else miss
           for o in run["outcomes"] if not o["abandoned"]]
    p = percentile(lat, 95.0)
    return None if p is None else p * 1e3
