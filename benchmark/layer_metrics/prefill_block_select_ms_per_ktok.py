"""Model step (prefill): device self-time under the scopes `compress` (a
sparse layer's keys mean-pooled over a kernel every stride) and
`block_select` (every query head's softmax over the pooled keys it may see,
summed over its kv head's group, the blocks' scores and the exact choice of
`topk` of them a row: the mask by blocks; ray_tpu/ops/sparse_attention.py::
block_mask) in the `jit_prefill` executions of the trace, over the thousands
of prompt tokens of the admits paired with them, as `prefill_ms_per_ktok`
counts the whole program. None for a program without the scopes.
device_trace."""

from benchmark import sala_trace


def read(run):
    return sala_trace.prefill_ms_per_ktok(run, sala_trace.SELECT)
