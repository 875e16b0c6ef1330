"""Model step (prefill): device self-time of the indexer's scoring and the
selection (scopes `indexer` and `select`, inside `attn`; on a TPU both are the
`index_select` kernel, under `select`) in the `jit_prefill` executions of the
trace, over the thousands of prompt tokens of the admits paired with them, as
`prefill_ms_per_ktok` counts the whole program. None for a program without
those scopes. device_trace."""

from benchmark import program_trace, sparse_attn_trace


def read(run):
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    tokens = sum(admit.args["prompt_tokens"] for admit, _, _ in pairs)
    each = sparse_attn_trace.by_scope(t, [r for _, r, _ in pairs]) \
        if tokens else []
    if not sparse_attn_trace.has(each):
        return None
    return sum(sparse_attn_trace.ns(d, ("indexer", "select"))
               for d in each) / 1e6 / (tokens / 1e3)
