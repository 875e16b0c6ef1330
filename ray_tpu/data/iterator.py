"""DataIterator: batch formation, the next batch made ahead, JAX conversion.

Analogue of the reference's iteration path (reference:
python/ray/data/iterator.py:71 DataIterator.iter_batches(prefetch_batches=1)
+ _internal/block_batching/, which forms the next batch on a thread while
the consumer works; iter_torch_batches → here iter_jax_batches, the BASELINE
north-star Arrow→DLPack→jax.Array host-zero-copy hop).

What is fetched ahead: with `prefetch_batches` > 0 (the default is 1, the
reference's) everything a batch takes (asking the source for a block
reference, the store `get`, the re-chunk, the format and, for JAX batches,
the `device_put`) runs on a daemon thread of the iterator's own, named
`PRODUCER_THREAD`, which hands finished batches over a queue of
`prefetch_batches`; the iterator the caller holds only takes from the queue.
With the batch the producer is making, at most `prefetch_batches` + 1 batches
exist beyond the one the consumer has. `prefetch_batches=0` makes each batch
inline, in the consumer's thread, when it is asked for. `prefetch_blocks` is
another thing: how many block REFERENCES the iterator holds beyond the one it
is reading. A reference is a name; holding it fetches, cuts and places
nothing.
"""

from __future__ import annotations

import functools
import queue
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import ray_tpu
from ray_tpu.data.block import BlockAccessor, concat_blocks
from ray_tpu.utils import tracing

PRODUCER_THREAD = "data-iter-producer"


def _format_batch(batch, batch_format: str):
    acc = BlockAccessor(batch)
    if batch_format == "numpy":
        return acc.to_numpy_batch()
    if batch_format == "pyarrow":
        return acc.to_arrow()
    if batch_format == "rows":
        return acc.to_rows()
    raise ValueError(f"unknown batch_format {batch_format!r}")


def _batches(ref_iter: Iterator[Any], *, batch_size: Optional[int],
             batch_format: str, prefetch_blocks: int,
             drop_last: bool) -> Iterator[Any]:
    """Stream blocks (holding `prefetch_blocks` references beyond the one
    being read) and re-chunk rows into batches of exactly batch_size (except
    possibly the last)."""
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
        yield _format_batch(carry, batch_format)


def _jax_batches(ref_iter: Iterator[Any], *, batch_size: Optional[int],
                 sharding: Optional[Any], prefetch_blocks: int,
                 drop_last: bool, global_batch: bool
                 ) -> Iterator[Dict[str, Any]]:
    import jax

    for batch in _batches(ref_iter, batch_size=batch_size,
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


_END = object()      # the producer's last word: the source ran dry


def _ahead(make: Callable[[], Iterator[Any]],
           prefetch_batches: int) -> Iterator[Any]:
    """The batches of `make()`, each made while the consumer works on the
    one before: `make`'s generator runs on a daemon thread (started at the
    first `next`, under the consumer's trace context) that puts finished
    batches on a queue of `prefetch_batches` and blocks when it is full, so
    it is never more than `prefetch_batches` + 1 ahead. An exception inside
    `make` is raised here, after the batches made before it. Closing,
    dropping or breaking out of the iterator ends the thread: it finishes
    the batch it is making and asks the source for nothing more. With
    `prefetch_batches` <= 0 this is `make()` itself, inline."""
    if prefetch_batches <= 0:
        return make()
    return _from_producer(make, prefetch_batches)


def _from_producer(make, prefetch_batches):
    q: "queue.Queue" = queue.Queue(maxsize=prefetch_batches)
    stop = threading.Event()
    ctx = tracing.context()

    def produce():
        batches = make()
        try:
            with tracing.under(ctx):
                for batch in batches:
                    q.put((batch, None))    # blocks while the queue is full
                    if stop.is_set():
                        return
            q.put(_END)
        except BaseException as e:  # noqa: BLE001 (the consumer raises it)
            q.put((None, e))
        finally:
            batches.close()                 # and with it the source

    threading.Thread(target=produce, name=PRODUCER_THREAD,
                     daemon=True).start()
    try:
        while True:
            with tracing.span("data.iter.take",
                              ready=int(not q.empty())) as took:
                t0 = time.monotonic_ns()
                item = q.get()
                took["waited_us"] = (time.monotonic_ns() - t0) // 1000
            if item is _END:
                return
            batch, error = item
            if error is not None:
                raise error
            yield batch
    finally:
        # A producer blocked in `put` wakes on the room made here, sees
        # `stop` (set first) and returns; one in the middle of a batch puts
        # it into the empty queue and does the same.
        stop.set()
        while not q.empty():
            q.get_nowait()


def iter_batches_from_refs(ref_iter: Iterator[Any], *, batch_size: Optional[int],
                           batch_format: str = "numpy",
                           prefetch_blocks: int = 2,
                           drop_last: bool = False,
                           prefetch_batches: int = 1) -> Iterator[Any]:
    """Batches of exactly batch_size rows (except possibly the last) over a
    stream of block references, the next `prefetch_batches` made on a thread
    while the consumer works (`_ahead`; 0: inline). `prefetch_blocks` is how
    many references are HELD beyond the one being read: it fetches nothing.

    Over `streaming_split(equal=True)` each consumer has a queue of its own
    at the coordinator, so running ahead takes nothing from another
    consumer; with `equal=False` a consumer may, at the very end, hold up to
    `prefetch_batches` + 1 batches another would have had."""
    return _ahead(functools.partial(
        _batches, ref_iter, batch_size=batch_size, batch_format=batch_format,
        prefetch_blocks=prefetch_blocks, drop_last=drop_last),
        prefetch_batches)


def iter_jax_batches_from_refs(ref_iter: Iterator[Any], *,
                               batch_size: Optional[int],
                               sharding: Optional[Any] = None,
                               prefetch_blocks: int = 2,
                               drop_last: bool = True,
                               global_batch: bool = False,
                               prefetch_batches: int = 1
                               ) -> Iterator[Dict[str, Any]]:
    """numpy batches → jax.Arrays.

    The host path is zero-copy: block bytes are mmapped from the shm store
    and deserialized as views; device transfer is the only copy. With
    ``sharding`` set, arrays are placed with jax.device_put(sharding); with
    ``global_batch=True`` (multi-host SPMD), each process's batch is treated
    as its shard of the global batch via
    jax.make_array_from_process_local_data (reference north star:
    Arrow → DLPack → jax.Array on the workers of a JaxTrainer).

    With ``prefetch_batches`` > 0 (default 1) the placement too is made on
    the producer's thread (`_ahead`; neither call is a collective), so the
    transfer overlaps the consumer's step, and up to ``prefetch_batches`` + 1
    batches beyond the consumer's live ON THE DEVICE: a user with large
    batches pays that HBM, or passes 0 for the inline path.
    """
    return _ahead(functools.partial(
        _jax_batches, ref_iter, batch_size=batch_size, sharding=sharding,
        prefetch_blocks=prefetch_blocks, drop_last=drop_last,
        global_batch=global_batch), prefetch_batches)
