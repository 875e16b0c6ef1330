"""Train scheduler (data/iterator.py): median host time inside `next(feed)`
per step of the window. host_clock."""

from benchmark.stats import median


def read(run):
    m = median([s["feed_wait_s"] for s in run["steps"]])
    return None if m is None else m * 1e3
