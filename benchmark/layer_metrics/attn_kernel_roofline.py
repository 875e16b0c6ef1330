"""Kernel (ops/attention.py): the least time the chip could take for the flash
forward and backward calls in the trace, over the device time they took.

A flash call is an `XLA Ops` event whose HLO text is a custom call to
`tpu_custom_call`; the program gives its kernels no name, so forward and
backward are told apart by their operands (3: q, k, v; 6: q, k, v, do, lse,
delta; the backward is two such calls, dq and dk/dv). Shapes are read from
the event's own text. Least time is the larger of operations over peak FLOP/s
and bytes over peak bytes/s (benchmark/flops.py, benchmark/peaks.py).
device_trace."""

import re

from benchmark import flops, peaks

SHAPE = re.compile(r"custom-call\(bf16\[(\d+),(\d+),(\d+)\]")


def read(run):
    data = run["trace_data"]
    if data is None:
        return None
    kind = run["device"]["kind"]
    f_peak = peaks.peak(kind, "bf16_flops_per_s")
    b_peak = peaks.peak(kind, "hbm_bytes_per_s")
    least = took = 0.0
    for hlo, s, e in data.chips[0].ops:
        if 'custom_call_target="tpu_custom_call"' not in hlo:
            continue
        m = SHAPE.search(hlo)
        if not m:
            return None      # not the kernel this reader knows
        bh, seq, hd = (int(x) for x in m.groups())
        operands = hlo.split("custom-call(", 1)[1].split(
            "), custom_call_target")[0].count("%")
        if operands == 3:
            ops, byts = flops.flash_call_ops_bytes(
                1, bh, seq, seq, hd, True, 2, backward=False)
        elif operands == 6:   # half of one backward
            ops, byts = flops.flash_call_ops_bytes(
                1, bh, seq, seq, hd, True, 2, backward=True)
            ops, byts = ops / 2, byts / 2
        else:
            return None
        least += max(ops / f_peak, byts / b_peak)
        took += (e - s) / 1e9
    return 100.0 * least / took if took else None
