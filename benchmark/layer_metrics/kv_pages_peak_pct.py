"""Scheduler, KV arena: the most pages reserved at once during the window
(`Engine.peak_pages_used`) over the pages there are. program_counter."""


def read(run):
    r = run["replica"]
    return 100.0 * r["peak_pages_used"] / (r["n_pages"] - 1)
