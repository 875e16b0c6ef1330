"""Service layer (serve/proxy.py, handle.py, core_worker's actor push,
replica.py): p95, over the window's requests, of the start of the request's
`serve.replica.call` span less the start of its `serve.proxy.request` span,
paired by the `trace_id` the proxy gives a request: the router's choice, the
actor push and the replica's entry. Every request of the 51 s, from spans of
two processes on one clock (`mono_ns`). program_span."""

from benchmark import timeline_record


def read(run):
    return timeline_record.path_p95_ms(run, "proxy", "call")
