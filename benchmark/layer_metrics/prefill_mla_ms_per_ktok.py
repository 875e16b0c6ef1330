"""Model step (prefill): device self-time of latent attention's scopes
(`attn_norm`, `qkv` with `q_latent`, `kv_latent` and `kv_up` inside it,
`rope`, `attn`, `attn_out`, `kv_write`;
ray_tpu/models/block.py::latent_attention_inputs) in the `jit_prefill`
executions of the trace, over the thousands of prompt tokens of the admits
paired with them, as `prefill_ms_per_ktok` counts the whole program. None
for a program without those scopes. device_trace."""

from benchmark import latent_trace, program_trace


def read(run):
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    tokens = sum(admit.args["prompt_tokens"] for admit, _, _ in pairs)
    each = latent_trace.by_scope(run, t, [r for _, r, _ in pairs]) \
        if tokens else []
    if not latent_trace.has(each):
        return None
    return sum(latent_trace.ns(d) for d in each) / 1e6 / (tokens / 1e3)
