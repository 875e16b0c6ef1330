"""Device self-time by scope for a mixed-attention stack's own scopes
(ray_tpu/serve/engine.py::_make_mixed_prefill_core, _mixed_layers;
ray_tpu/ops/slot_state.py), and the two counts its readers share.

`program_trace.py` reduces a trace by a fixed vocabulary of scope names, in
which `window_attn` and `full_attn` (inside `attn`: the attention kernel of a
window or a full layer and nothing else) and `window_write` (a window
layer's ring written, beside `kv_write`) do not appear. The readers of the
window metrics need the deeper names, and sums over chosen executions, as
`moe_trace.py`'s, `ssm_trace.py`'s and `latent_trace.py`'s do for their
layers: same trace, same events, same rule (an instruction's time less its
children's, charged to the deepest scope of its path that is in the
vocabulary; XLA's `ragged-dot` kernels, which carry no scope, to `experts`).
A program without these scopes gives dictionaries without them, and every
reader then returns None.

    python3 benchmark/window_trace.py benchmark/out/<cell>/<seed>/trace

prints, for `jit_prefill` and `jit_decode`, the mean device self-time an
execution by scope under this vocabulary.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import moe_trace, peaks, program_trace  # noqa: E402
from benchmark.stats import median  # noqa: E402

SCOPES = ("window_attn", "full_attn", "window_write")
VOCABULARY = moe_trace.VOCABULARY + SCOPES
BYTES = {"bfloat16": 2, "float32": 4}
_WORD = re.compile(r"[A-Za-z_]\w*")


def deepest_scope(path: str) -> str:
    """`jit(decode)/.../layers/while/body/attn/window_attn/dot_general:` ->
    `window_attn`. The last component is the primitive, never a scope."""
    for part in reversed(path.split("/")[:-1]):
        for word in _WORD.findall(part):
            if word in VOCABULARY:
                return word
    return ""


def by_scope(run: Optional[dict], t: program_trace.ProgramTrace,
             executions: Sequence[Tuple[str, float, float]]
             ) -> List[Dict[str, float]]:
    """For each execution (name, start, end) of a program on chip 0, in the
    order given (by start), nanoseconds of device self-time by scope."""
    kernels = moe_trace.grouped_matmuls(run) if run else []
    ops, each, i = t.ops, [], 0
    for _, ms, me in executions:
        while i < len(ops) and ops[i][1] < ms:
            i += 1
        out: Dict[str, float] = {}
        stack: List[List] = []      # [scope, end, self_ns]
        while i < len(ops) and ops[i][1] < me:
            path, s, e = ops[i]
            while stack and stack[-1][1] <= s:
                scope, _, own = stack.pop()
                out[scope] = out.get(scope, 0.0) + own
            if stack:
                stack[-1][2] -= e - s
            scope = deepest_scope(path)
            if not scope and kernels and moe_trace._starts_at(kernels, s):
                scope = "experts"
            stack.append([scope, e, e - s])
            i += 1
        for scope, _, own in stack:
            out[scope] = out.get(scope, 0.0) + own
        each.append(out)
    return each


def has(each: List[Dict[str, float]]) -> bool:
    return any(s in d for d in each for s in SCOPES)


def prefill_scope(run: dict, scopes: Sequence[str]
                  ) -> Optional[Tuple[List[int], float]]:
    """(the prompt tokens of each prefill paired with its admit span, the
    device self-time in seconds those executions spent under `scopes`), or
    None for a trace without this stack's scopes."""
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    each = by_scope(run, t, [r for _, r, _ in pairs]) if pairs else []
    if not has(each):
        return None
    return ([admit.args["prompt_tokens"] for admit, _, _ in pairs],
            sum(d.get(s, 0.0) for d in each for s in scopes) / 1e9)


def decode_scope(run: dict, scopes: Sequence[str], arg: str
                 ) -> Optional[Tuple[float, float]]:
    """(the median of the `serve.engine.decode_dispatch` spans' argument
    `arg`, the median device self-time in seconds a whole `jit_decode`
    execution spent under `scopes`), or None for a trace without this
    stack's scopes or that argument."""
    t = program_trace.load(run)
    spans = [s.args[arg]
             for s in (t.named("serve.engine.decode_dispatch") if t else [])
             if arg in s.args]
    each = by_scope(run, t, t.whole_modules("jit_decode")) if spans else []
    if not has(each):
        return None
    return (median(spans),
            median([sum(d.get(s, 0.0) for s in scopes) for d in each]) / 1e9)


def prefill_roofline_pct(run: dict, window: bool) -> Optional[float]:
    """The least time the chip could take for one kind of layer's prompt
    attention in the paired prefills, over the device time its scope took.
    Least time a prompt a layer is the larger of operations over peak FLOP/s
    and bytes over peak HBM bytes/s of the adapter's
    `counts.prefill_attn_ops_bytes` at the PROMPT's tokens (the bucket's
    padding rows are computed by the kernel and not counted: the share can
    only under-read), times that kind's layers."""
    from benchmark import models
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    read = prefill_scope(run, ["window_attn" if window else "full_attn"])
    if read is None or not hasattr(counts, "prefill_attn_ops_bytes"):
        return None
    tokens, took_s = read
    kind = run["device"]["kind"]
    f_peak = peaks.peak(kind, "bf16_flops_per_s")
    b_peak = peaks.peak(kind, "hbm_bytes_per_s")
    layers = counts.attention_layers(m)[1 if window else 0]
    least = 0.0
    for n in tokens:
        ops, byts = counts.prefill_attn_ops_bytes(
            m, n, window, BYTES[m["dtypes"]["activations"]])
        least += layers * max(ops / f_peak, byts / b_peak)
    return 100.0 * least / took_s if took_s else None


def decode_roofline_pct(run: dict, window: bool) -> Optional[float]:
    """The least time the chip could take to read the cached rows one kind
    of layer's decode attention needs in a `jit_decode` execution, over the
    device self-time the execution spent in that kind's attention scope.
    Decode attention is bound by bytes: least time is the adapter's
    `counts.decode_attn_bytes` over peak HBM bytes/s. A window layer's rows
    are the dispatch span's `window_kv_tokens` (summed over the chunk's steps
    already); a full layer's its `live_kv_tokens` (positions at the chunk's
    START: every step adds one a slot, so the share can only under-read)
    times the chunk's steps."""
    from benchmark import models
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    read = decode_scope(run, ["window_attn" if window else "full_attn"],
                        "window_kv_tokens" if window else "live_kv_tokens")
    if read is None or not hasattr(counts, "decode_attn_bytes"):
        return None
    rows, took_s = read
    if not window:
        rows *= m["deployment"]["engine"]["decode_chunk"]
    layers = counts.attention_layers(m)[1 if window else 0]
    byts = layers * counts.decode_attn_bytes(
        m, rows, window, BYTES[m["dtypes"]["activations"]])
    least_s = byts / peaks.peak(run["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / took_s if took_s else None


def main(argv: List[str]) -> int:
    t = program_trace.load_path(argv[1])
    if t is None:
        print("no trace under", argv[1])
        return 1
    for program in ("jit_prefill", "jit_decode"):
        runs = t.whole_modules(program)
        each = by_scope(None, t, runs)
        print(f"program {program}: {len(runs)} whole executions")
        total: Dict[str, float] = {}
        for d in each:
            for scope, own in d.items():
                total[scope] = total.get(scope, 0.0) + own
        for scope, own in sorted(total.items(), key=lambda kv: -kv[1]):
            print(f"    {scope or '(no scope)':<16s}"
                  f"{own / 1e6 / max(len(runs), 1):10.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
