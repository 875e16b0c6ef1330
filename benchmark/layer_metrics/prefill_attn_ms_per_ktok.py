"""Model step (prefill): device self-time of the `attn` scope (the flash
kernel over a prompt, ray_tpu/models/serving.py) in the `jit_prefill`
executions of the trace, over the thousands of prompt tokens of the admits
paired with them, as `prefill_ssm_ms_per_ktok` and `prefill_moe_ms_per_ktok`
read their parts: in a stack whose layers are ONE part each, the mixer, the
experts and attention are separate LAYERS, and these three are a prompt's
cost by kind of layer (the projections around attention, `qkv` and
`attn_out`, are not in it, as the other two readers' scopes hold their
projections: read `prefill_ms_per_ktok` less the three for the rest). Read
through `ssm_trace.by_scope`, whose vocabulary holds `attn` beside the
mixer's scopes; None for a program without the mixer's scopes (the other
stacks' attention has readers of its own). device_trace."""

from benchmark import program_trace, ssm_trace


def read(run):
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    tokens = sum(admit.args["prompt_tokens"] for admit, _, _ in pairs)
    each = ssm_trace.by_scope(t, [r for _, r, _ in pairs]) if tokens else []
    if not ssm_trace.has(each):
        return None
    return sum(d.get("attn", 0.0) for d in each) / 1e6 / (tokens / 1e3)
