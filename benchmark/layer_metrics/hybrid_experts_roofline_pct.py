"""Kernel (the experts' grouped matmuls under the `experts` scope, in a stack
where not every layer is sparse): the least time the chip could take for
that scope's work over the device time the scope took, prefill and decode
together, as `moe_experts_roofline_pct` counts it (operations of the LIVE
assignments, bytes of the experts TOUCHED from the program's counters, the
larger of operations over peak FLOP/s and bytes over peak HBM bytes/s a
layer) but over the SPARSE layers of the adapter's `counts.layers(m)`, not
`num_hidden_layers`: the leading dense layers have no experts.

* prefill: each `jit_prefill` execution paired with its admit
  (`prompt_tokens`) and its `serve.engine.prefill_experts` span (`touched`:
  distinct experts, summed over the sparse layers);
* decode: the whole `jit_decode` executions; live rows a step and distinct
  experts a layer a step are the means of `active` and `experts_touched` (a
  chunk's sum over steps and layers, reported by the next dispatch) over the
  trace's `serve.engine.decode_dispatch` spans.

None without those scopes and counters. device_trace."""

from benchmark import conv_trace, models


def read(run):
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    pre, dec = conv_trace.prefills(run), conv_trace.decodes(run)
    if pre is None or dec is None or not hasattr(counts, "layers"):
        return None
    sparse, k = counts.layers(m)[1], m["num_experts_per_tok"]
    chunk = m["deployment"]["engine"]["decode_chunk"]
    wb = conv_trace.BYTES[m["dtypes"]["params"]]
    ab = conv_trace.BYTES[m["dtypes"]["activations"]]
    f_peak, b_peak = conv_trace.device_peaks(run)

    def least_s(rows, touched):
        """One layer over `rows` live tokens touching `touched` experts."""
        ops, byts = counts.experts_ops_bytes(m, rows * k, touched, wb, ab)
        return max(ops / f_peak, byts / b_peak)

    t, pairs = pre
    least = took = 0.0
    touched_of = {s.args.get("rid"): s.args.get("touched")
                  for s in t.named("serve.engine.prefill_experts")}
    for admit, scopes in pairs:
        touched = touched_of.get(admit.args["rid"])
        if touched:
            least += sparse * least_s(admit.args["prompt_tokens"],
                                      touched / sparse)
            took += scopes.get("experts", 0.0) / 1e9
    _, each, spans = dec
    chunks = [s for s in spans if s.args.get("experts_touched")]
    if chunks and each:
        rows = sum(s.args["active"] for s in chunks) / len(chunks)
        touched = sum(s.args["experts_touched"] for s in chunks) \
            / len(chunks) / (chunk * sparse)
        least += len(each) * chunk * sparse * least_s(rows, touched)
        took += sum(d.get("experts", 0.0) for d in each) / 1e9
    return 100.0 * least / took if took else None
