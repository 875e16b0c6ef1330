"""Kernel (the grouped matmuls of a SHARE of the experts:
`jax.lax.ragged_dot` over the held experts' groups under the `experts`
scope, XLA's `ragged-dot` kernels found by name where they carry no scope,
benchmark/latent_trace.py): the least time the chip could take for that
scope's work, over the device time the scope took, prefill and decode
together (so each weighs by its time).

What this chip computes is counted and nothing an absent chip would (where
`moe_experts_roofline_pct` counts `prompt_tokens x experts a token`
assignments a layer, every one of them local). Least time of one layer's
grouped matmuls is the larger of operations over peak FLOP/s and bytes over
peak bytes/s (the adapter's `counts.experts_ops_bytes`, benchmark/peaks.py):
operations of the LOCAL live assignments, bytes of the held experts TOUCHED
and of the rows moved, both from the program's counters:

* prefill: each `jit_prefill` execution paired with its
  `serve.engine.prefill_experts` span (`local`: assignments to held experts,
  `touched`: distinct held experts, both summed over the sparse layers);
* decode: the whole `jit_decode` executions; local assignments and distinct
  experts a sparse layer a step are the means of `local_assignments` and
  `experts_touched` (a chunk's sums over steps and layers, reported by the
  next dispatch) over the trace's `serve.engine.decode_dispatch` spans.

None without those scopes and counters. device_trace."""

from benchmark import latent_trace, models, peaks, program_trace

BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    t = program_trace.load(run)
    if t is None:
        return None
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    if not hasattr(counts, "experts_ops_bytes") \
            or not hasattr(counts, "layers"):
        return None
    _, sparse = counts.layers(m)
    chunk = m["deployment"]["engine"]["decode_chunk"]
    wb, ab = BYTES[m["dtypes"]["params"]], BYTES[m["dtypes"]["activations"]]
    kind = run["device"]["kind"]
    f_peak = peaks.peak(kind, "bf16_flops_per_s")
    b_peak = peaks.peak(kind, "hbm_bytes_per_s")

    def least_s(local, touched):
        """One layer over `local` assignments touching `touched` experts."""
        ops, byts = counts.experts_ops_bytes(m, local, touched, wb, ab)
        return max(ops / f_peak, byts / b_peak)

    least = took = 0.0
    share_of = {s.args.get("rid"): s.args
                for s in t.named("serve.engine.prefill_experts")
                if "local" in s.args}
    pairs = [p for p in t.prefills() if p[0].args["rid"] in share_of]
    for (admit, _, _), scopes in zip(
            pairs, latent_trace.by_scope(run, t, [r for _, r, _ in pairs])):
        args = share_of[admit.args["rid"]]
        least += sparse * least_s(args["local"] / sparse,
                                  args["touched"] / sparse)
        took += scopes.get("experts", 0.0) / 1e9
    chunks = [s for s in t.named("serve.engine.decode_dispatch")
              if "local_assignments" in s.args
              and s.args.get("experts_touched")]
    decodes = latent_trace.by_scope(run, t, t.whole_modules("jit_decode"))
    if chunks and decodes:
        steps = chunk * sparse
        local = sum(s.args["local_assignments"] for s in chunks) \
            / len(chunks) / steps
        touched = sum(s.args["experts_touched"] for s in chunks) \
            / len(chunks) / steps
        least += len(decodes) * steps * least_s(local, touched)
        took += sum(d.get("experts", 0.0) for d in decodes) / 1e9
    if not share_of and not chunks:
        return None
    return 100.0 * least / took if took else None
