"""Model step (prefill): device self-time of the state-space mixer's five
scopes (`ssm_in`, `conv`, `ssm_params`, `scan`, `ssm_out`;
ray_tpu/models/block.py::mamba_mixer) in the `jit_prefill` executions of the
trace, over the thousands of prompt tokens of the admits paired with them, as
`prefill_ms_per_ktok` counts the whole program. None for a program without
those scopes. device_trace."""

from benchmark import program_trace, ssm_trace


def read(run):
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    tokens = sum(admit.args["prompt_tokens"] for admit, _, _ in pairs)
    each = ssm_trace.by_scope(t, [r for _, r, _ in pairs]) if tokens else []
    if not ssm_trace.has(each):
        return None
    return sum(ssm_trace.ns(d) for d in each) / 1e6 / (tokens / 1e3)
