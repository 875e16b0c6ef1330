"""DataIterator: batch formation + prefetch + JAX conversion.

Analogue of the reference's iteration path (reference:
python/ray/data/iterator.py:71 DataIterator.iter_batches +
_internal/block_batching/ prefetch windows; iter_torch_batches →
here iter_jax_batches, the BASELINE north-star Arrow→DLPack→jax.Array
host-zero-copy hop).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

import ray_tpu
from ray_tpu.data.block import BlockAccessor, concat_blocks
from ray_tpu.utils import tracing


def _format_batch(batch, batch_format: str):
    acc = BlockAccessor(batch)
    if batch_format == "numpy":
        return acc.to_numpy_batch()
    if batch_format == "pyarrow":
        return acc.to_arrow()
    if batch_format == "rows":
        return acc.to_rows()
    raise ValueError(f"unknown batch_format {batch_format!r}")


def iter_batches_from_refs(ref_iter: Iterator[Any], *, batch_size: Optional[int],
                           batch_format: str = "numpy",
                           prefetch_blocks: int = 2,
                           drop_last: bool = False) -> Iterator[Any]:
    """Stream blocks (prefetching refs ahead) and re-chunk rows into batches
    of exactly batch_size (except possibly the last)."""
    window: List[Any] = []

    def fill(it):
        while len(window) < prefetch_blocks + 1:
            try:
                window.append(next(it))
            except StopIteration:
                return False
        return True

    it = iter(ref_iter)
    carry = None  # leftover rows as a block
    while True:
        # The dataset's executor hands out references one at a time: for a
        # streaming split that is a call to its coordinator.
        with tracing.span("data.iter.next_ref", held=len(window)):
            fill(it)
        if not window:
            break
        with tracing.span("data.iter.get_block") as got:
            block = ray_tpu.get(window.pop(0))
            got["rows"] = BlockAccessor(block).num_rows()
        if carry is not None:
            block = concat_blocks([carry, block])
            carry = None
        acc = BlockAccessor(block)
        n = acc.num_rows()
        if batch_size is None:
            if n:
                yield _format_batch(block, batch_format)
            continue
        start = 0
        while n - start >= batch_size:
            with tracing.span("data.iter.format", rows=batch_size):
                batch = _format_batch(acc.slice(start, start + batch_size),
                                      batch_format)
            yield batch
            start += batch_size
        if start < n:
            carry = acc.slice(start, n)
    if carry is not None and BlockAccessor(carry).num_rows() and not drop_last:
        if batch_size is None or not drop_last:
            yield _format_batch(carry, batch_format)


def iter_jax_batches_from_refs(ref_iter: Iterator[Any], *,
                               batch_size: Optional[int],
                               sharding: Optional[Any] = None,
                               prefetch_blocks: int = 2,
                               drop_last: bool = True,
                               global_batch: bool = False
                               ) -> Iterator[Dict[str, Any]]:
    """numpy batches → jax.Arrays.

    The host path is zero-copy: block bytes are mmapped from the shm store
    and deserialized as views; device transfer is the only copy. With
    ``sharding`` set, arrays are placed with jax.device_put(sharding); with
    ``global_batch=True`` (multi-host SPMD), each process's batch is treated
    as its shard of the global batch via
    jax.make_array_from_process_local_data (reference north star:
    Arrow → DLPack → jax.Array on the workers of a JaxTrainer).
    """
    import jax

    for batch in iter_batches_from_refs(ref_iter, batch_size=batch_size,
                                        batch_format="numpy",
                                        prefetch_blocks=prefetch_blocks,
                                        drop_last=drop_last):
        n = len(next(iter(batch.values()))) if batch else 0
        if batch_size is not None and drop_last and n != batch_size:
            continue
        with tracing.span(
                "data.iter.device_put", rows=n,
                bytes=sum(int(v.nbytes) for v in batch.values())):
            if sharding is not None and global_batch:
                out = {k: jax.make_array_from_process_local_data(sharding, v)
                       for k, v in batch.items()}
            elif sharding is not None:
                out = {k: jax.device_put(v, sharding)
                       for k, v in batch.items()}
            else:
                out = {k: jax.device_put(v) for k, v in batch.items()}
        yield out
