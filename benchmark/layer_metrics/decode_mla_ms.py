"""Model step (decode): device self-time a decode step of latent attention's
scopes (`attn_norm`, `qkv` with `q_latent`, `kv_latent` and `absorb` inside
it, `rope`, `kv_write`, `attn`: the kernel `paged_latent_decode`, `attn_out`
with the value's `absorb` inside it; all the layers) in `jit_decode`, the
median over the whole executions of the trace. None for a program without
those scopes. device_trace."""

from benchmark import latent_trace, program_trace
from benchmark.stats import median


def read(run):
    t = program_trace.load(run)
    each = latent_trace.by_scope(run, t, t.whole_modules("jit_decode")) \
        if t else []
    if not latent_trace.has(each):
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return median([latent_trace.ns(d) for d in each]) / 1e6 / chunk
