"""Service layer (serve/proxy.py, serve/handle.py, serve/replica.py): median
of the client's time to first byte from SEND, less the time from call entry to
first token stamped inside the replica. What is left is proxy, router, actor
call and stream transport, both ways. host_clock on one machine."""

from benchmark.stats import median


def read(run):
    stamps = run["replica"]["stamps"]
    over = []
    for o in run["outcomes"]:
        s = stamps.get(str(o["index"]))
        if o["ok"] and s:
            over.append((o["t_first"] - o["t_send"]) - (s[1] - s[0]))
    m = median(over)
    return None if m is None else m * 1e3
