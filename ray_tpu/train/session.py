"""Worker-side train session: report() + get_context().

Analogue of the reference's train session (reference: python/ray/train/
_internal/session.py get_session / ray.train.report, v2 via
train/v2/_internal/execution/worker_group/thread_runner.py): the user's
train loop runs in a thread inside the worker actor and communicates with
the controller through this module.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple


class TrainContext:
    def __init__(self, rank: int, world_size: int,
                 experiment_name: str = "", storage_path: str = "",
                 restored_checkpoint: Optional[Any] = None,
                 dataset_shards: Optional[Dict[str, Any]] = None):
        self.rank = rank
        self.world_size = world_size
        self.experiment_name = experiment_name
        self.storage_path = storage_path
        self._restored_checkpoint = restored_checkpoint
        self._dataset_shards = dict(dataset_shards or {})

    def get_world_rank(self) -> int:
        return self.rank

    def get_world_size(self) -> int:
        return self.world_size

    def get_checkpoint(self) -> Optional[Any]:
        """Checkpoint to resume from (set on group restart), else None."""
        return self._restored_checkpoint

    def get_dataset_shard(self, name: str = "train"):
        """This worker's DataIterator for the trainer's datasets= entry
        (reference: ray.train.get_dataset_shard)."""
        if name not in self._dataset_shards:
            raise KeyError(
                f"no dataset shard {name!r}; trainer datasets= had "
                f"{sorted(self._dataset_shards)}")
        return self._dataset_shards[name]


class _Session:
    def __init__(self, ctx: TrainContext):
        self.ctx = ctx
        self.lock = threading.Lock()
        # (metrics, checkpoint) tuples not yet drained by the controller.
        self.reported: List[Tuple[Dict[str, Any], Optional[Any]]] = []
        self.finished = False
        self.error: Optional[str] = None

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Any] = None) -> None:
        with self.lock:
            self.reported.append((dict(metrics), checkpoint))

    def drain(self) -> List[Tuple[Dict[str, Any], Optional[Any]]]:
        with self.lock:
            out = self.reported
            self.reported = []
            return out


_session: Optional[_Session] = None


def _start_session(ctx: TrainContext) -> _Session:
    global _session
    _session = _Session(ctx)
    return _session


def _end_session() -> None:
    global _session, _async_ckptr
    _session = None
    # Flush any in-flight async save: the worker reporting "finished"
    # (and getting killed) must not strand an uncommitted checkpoint.
    ckptr, _async_ckptr = _async_ckptr, None
    if ckptr is not None:
        try:
            ckptr.close()
        except Exception:
            from ray_tpu.utils import get_logger
            get_logger("train.session").warning(
                "async checkpoint flush at session end failed",
                exc_info=True)


def get_context() -> TrainContext:
    if _session is None:
        raise RuntimeError("not inside a train worker session")
    return _session.ctx


def get_dataset_shard(name: str = "train"):
    """This worker's dataset shard (reference: ray.train.get_dataset_shard)."""
    return get_context().get_dataset_shard(name)


def profile(directory: Optional[str] = None):
    """Context manager: capture a JAX profiler trace (XPlane, viewable in
    TensorBoard/XProf) of the device and of `ray_tpu.utils.tracing` spans
    (`train.step`, `data.iter.*`), without Python's own frames. It goes under
    `directory`, or under the run's storage path; with neither it refuses
    rather than write somewhere nobody will look.

        with ray_tpu.train.profile():
            state, m = step_fn(state, batch)
    """
    import contextlib
    import os

    @contextlib.contextmanager
    def _ctx():
        import jax
        ctx = get_context()
        base = directory or ctx.storage_path
        if not base:
            raise RuntimeError("profile() needs a directory: pass one, or "
                               "set RunConfig.storage_path")
        out = os.path.join(base, ctx.experiment_name or "train_run",
                           f"profile-rank{ctx.rank}")
        os.makedirs(out, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()

    return _ctx()


def save_checkpoint(state: Any, step: int,
                    metrics: Optional[Dict[str, Any]] = None, *,
                    block: bool = True):
    """Sharded save of a jax pytree into the run's storage path; call from
    EVERY rank (per-host shard writes + commit barrier), then report the
    returned handle: ``report(metrics, checkpoint=save_checkpoint(...))``.

    block=False (async, SURVEY §5.4 Orbax pattern): only the
    device->host snapshot runs here; file writes + the commit barrier
    run on a background thread and a Future[Checkpoint] is returned —
    call ``.result()`` (or save again, which serializes) before
    reporting it."""
    from ray_tpu.train.checkpointing import run_dir
    from ray_tpu.train.checkpointing import save_checkpoint as _save
    ctx = get_context()
    if not ctx.storage_path:
        raise RuntimeError("RunConfig.storage_path is not set")
    directory = run_dir(ctx.storage_path, ctx.experiment_name)
    if block:
        return _save(directory, state, step, metrics)
    global _async_ckptr
    if _async_ckptr is None:
        from ray_tpu.train.checkpointing import AsyncCheckpointer
        _async_ckptr = AsyncCheckpointer()
    return _async_ckptr.save(directory, state, step, metrics)


_async_ckptr = None


def report(metrics: Dict[str, Any], checkpoint: Optional[Any] = None) -> None:
    """Report metrics (and optionally a checkpoint) from the train loop.

    Reference analogue: ray.train.report (train/_internal/session.py).
    """
    if _session is None:
        raise RuntimeError("report() called outside a train worker session")
    _session.report(metrics, checkpoint)
