"""Model step (decode): device self-time a decode step of a block-sparse
layer's attention: the scopes `compress` (the step's key into the slot's
pooled keys), `block_select` (the scores against them, the choice of blocks,
each kv head's table of its selected pages) and `block_sparse_attn` (the
`paged_decode` kernel over those pages, a kv head a call;
ray_tpu/ops/sparse_attention.py::block_sparse_decode) in `jit_decode`, the
median over the whole executions of the trace. None for a program without the
scopes. device_trace."""

from benchmark import sala_trace


def read(run):
    return sala_trace.decode_step_ms(
        run, sala_trace.SELECT + sala_trace.SPARSE)
