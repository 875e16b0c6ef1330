"""Service layer, the way back: p95, over the window's requests, of the
instant the proxy wrote the first item to the client (its span's start plus
`first_chunk_us`) less the end of the request's `serve.engine.emit` span
(`kind` first), paired by `trace_id`: the replica's producer thread, the
stream's transport and the proxy's pump. program_span."""

from benchmark import timeline_record


def read(run):
    return timeline_record.path_p95_ms(run, "first", "written")
