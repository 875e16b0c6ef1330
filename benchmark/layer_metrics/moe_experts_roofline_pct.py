"""Kernel (the experts' grouped matmuls: `jax.lax.ragged_dot` under the
`experts` scope, XLA's `ragged-dot` kernels found by name where they carry no
scope, benchmark/moe_trace.py): the least time the chip could take for that scope's work,
over the device time the scope took, prefill and decode together (so each
weighs by its time).

Least time of one layer's grouped matmuls is the larger of operations over
peak FLOP/s and bytes over peak bytes/s (the adapter's
`counts.experts_ops_bytes`, benchmark/peaks.py). Operations are those of the
LIVE assignments (a bucket's padding and idle slots are computed by the
program and are not work); bytes are the weights of the experts TOUCHED, from
the program's counters, and the rows moved:

* prefill: each `jit_prefill` execution paired with its admit
  (`prompt_tokens`) and its `serve.engine.prefill_experts` span (`touched`:
  distinct experts, summed over the layers);
* decode: the whole `jit_decode` executions; live rows a step and distinct
  experts a layer a step are the means of `active` and `experts_touched`
  (a chunk's sum over steps and layers, reported by the next dispatch) over
  the trace's `serve.engine.decode_dispatch` spans.

None without those scopes and counters. device_trace."""

from benchmark import models, moe_trace, peaks, program_trace

BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    t = program_trace.load(run)
    if t is None:
        return None
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    if not hasattr(counts, "experts_ops_bytes"):
        return None
    layers, k = m["num_hidden_layers"], m["num_experts_per_tok"]
    chunk = m["deployment"]["engine"]["decode_chunk"]
    wb, ab = BYTES[m["dtypes"]["params"]], BYTES[m["dtypes"]["activations"]]
    kind = run["device"]["kind"]
    f_peak = peaks.peak(kind, "bf16_flops_per_s")
    b_peak = peaks.peak(kind, "hbm_bytes_per_s")

    def least_s(rows, touched):
        """One layer over `rows` live tokens touching `touched` experts."""
        ops, byts = counts.experts_ops_bytes(m, rows * k, touched, wb, ab)
        return max(ops / f_peak, byts / b_peak)

    least = took = 0.0
    touched_of = {s.args.get("rid"): s.args.get("touched")
                  for s in t.named("serve.engine.prefill_experts")}
    pairs = [p for p in t.prefills() if touched_of.get(p[0].args["rid"])]
    for (admit, _, _), scopes in zip(
            pairs, moe_trace.by_scope(run, t, [r for _, r, _ in pairs])):
        least += layers * least_s(admit.args["prompt_tokens"],
                                  touched_of[admit.args["rid"]] / layers)
        took += scopes.get("experts", 0.0) / 1e9
    chunks = [s for s in t.named("serve.engine.decode_dispatch")
              if s.args.get("experts_touched")]
    decodes = moe_trace.by_scope(run, t, t.whole_modules("jit_decode"))
    if chunks and decodes:
        rows = sum(s.args["active"] for s in chunks) / len(chunks)
        touched = sum(s.args["experts_touched"] for s in chunks) \
            / len(chunks) / (chunk * layers)
        least += len(decodes) * chunk * layers * least_s(rows, touched)
        took += sum(d.get("experts", 0.0) for d in decodes) / 1e9
    return 100.0 * least / took if took else None
