"""Kernel (decode attention over the paged KV cache, `paged_decode` in
ray_tpu/ops/attention.py, under the `attn` scope of `jit_decode`): the least
time the chip could take to read the live K and V of a `jit_decode`
execution, over the device self-time that execution spent under `attn`.

The kernel is bound by bytes (one query token a slot: two operations a byte
read), so least time is bytes over peak HBM bytes/s (benchmark/peaks.py):

    median `live_kv_tokens` of the trace's `serve.engine.decode_dispatch`
    spans (positions the active slots held when the chunk was dispatched,
    the program's counter) x `decode_chunk` steps x layers x 2 (K and V)
    x kv heads x head_dim x bytes an element

and the time taken is what `decode_attn_ms` reads, before its division by
the chunk. The count is of tokens at the chunk's START (every step adds one
a slot) and is not rounded up to pages, so the share can only under-read:
over 100 is a fault in this reader. None for a program whose spans carry no
`live_kv_tokens` (before PR 28, whose `attn` scope also read every slot's
whole block table). device_trace."""

from benchmark import peaks, program_trace
from benchmark.stats import median

BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    t = program_trace.load(run)
    live = [s.args["live_kv_tokens"]
            for s in (t.named("serve.engine.decode_dispatch") if t else [])
            if "live_kv_tokens" in s.args]
    took_ms = program_trace.scoped_ms(run, "jit_decode", ("attn",))
    if not live or not took_ms:
        return None
    m = run["config"]
    byts = (median(live) * m["deployment"]["engine"]["decode_chunk"]
            * m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
            * m["head_dim"] * BYTES[m["dtypes"]["activations"]])
    least_s = byts / peaks.peak(run["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (took_ms / 1e3)
