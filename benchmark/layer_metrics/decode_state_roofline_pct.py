"""Kernel (the recurrent state's update in a decode step,
ray_tpu/ops/ssm.py::ssm_step and ray_tpu/ops/slot_state.py, scope `scan` of
`jit_decode`): the least time the chip could take to move the active slots'
recurrent state of a `jit_decode` execution, over the device self-time that
execution spent under `scan`.

The update is bound by bytes (a dozen operations an element of state read
and written), so least time is bytes over peak HBM bytes/s
(benchmark/peaks.py): the adapter's `counts.decode_state_bytes` for the
median `active` of the trace's `serve.engine.decode_dispatch` spans (the
slots whose state a chunk moves, the program's counter) times the chunk's
steps: each step reads and writes every state-space layer's state of every
active slot once. An idle slot's state is read and written too by the
program (a select keeps it) and is not counted, so the share can only
under-read: over 100 is a fault in this reader. None for a program without
the scope or a model whose counts have no state. device_trace."""

from benchmark import models, peaks, program_trace, ssm_trace
from benchmark.stats import median

BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    t = program_trace.load(run)
    active = [s.args["active"]
              for s in (t.named("serve.engine.decode_dispatch") if t else [])
              if "active" in s.args]
    each = ssm_trace.by_scope(t, t.whole_modules("jit_decode")) \
        if active else []
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    if not ssm_trace.has(each) or not hasattr(counts, "decode_state_bytes"):
        return None
    chunk = m["deployment"]["engine"]["decode_chunk"]
    byts = counts.decode_state_bytes(m, median(active) * chunk,
                                     BYTES[m["dtypes"]["activations"]])
    least_s = byts / peaks.peak(run["device"]["kind"], "hbm_bytes_per_s")
    took_s = median([d.get("scan", 0.0) for d in each]) / 1e9
    return 100.0 * least_s / took_s if took_s else None
