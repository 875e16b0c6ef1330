"""Model step (prefill): the whole prefill program's share of the chip's
matrix peak: the operations the prompts of the trace's `jit_prefill`
executions require (the adapter's `counts.prefill_flops` at each paired
`serve.engine.admit` span's `prompt_tokens`: every position through the
blocks, causal attention at its lower triangle, the recurrence as the least
that computes it, the head for ONE row; of the experts, the assignments to
HELD experts that the program counted for that prompt, the `local` of its
`serve.engine.prefill_experts` span, where the adapter's count takes them and
the span carries them, else their expectation under even routing) over the
device time of those executions and the peak FLOP/s (benchmark/peaks.py).
What `decode_mfu_pct` is to a step and `train_mfu_pct` to a train step. A
bucket's padding is computed by the program and is no work of the prompt's,
so the share can only under-read: over 100 is a fault in this reader. None
without the pairing, or for an adapter whose counts have no `layers` (a
stack this reader was not written for). device_trace."""

import inspect

from benchmark import models, peaks, program_trace


def read(run):
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    if not pairs or not hasattr(counts, "layers"):
        return None
    takes_local = "local" in inspect.signature(counts.prefill_flops).parameters
    local_of = {s.args.get("rid"): s.args["local"]
                for s in t.named("serve.engine.prefill_experts")
                if "local" in s.args} if takes_local else {}
    ops = took = 0.0
    for admit, (_, start, end), _ in pairs:
        rid = admit.args["rid"]
        more = {"local": local_of[rid]} if rid in local_of else {}
        ops += counts.prefill_flops(m, admit.args["prompt_tokens"], **more)
        took += (end - start) / 1e9
    peak = peaks.peak(run["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * ops / peak / took if took else None
