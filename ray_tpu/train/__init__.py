from ray_tpu.train.api_config import (CheckpointConfig, FailureConfig,
                                      Result, RunConfig, ScalingConfig)
from ray_tpu.train.checkpointing import (AsyncCheckpointer, Checkpoint,
                                         CheckpointManager,
                                         load_checkpoint_host,
                                         restore_checkpoint)
from ray_tpu.train.jax_trainer import JaxTrainer
from ray_tpu.train.scaling_policy import (ElasticScalingPolicy,
                                          FixedScalingPolicy,
                                          ScalingPolicy)
from ray_tpu.train.session import (get_context, get_dataset_shard, profile,
                                   report, save_checkpoint)

_SPMD_NAMES = ("default_optimizer", "make_train_fns", "state_shardings")


def __getattr__(name):
    # Lazy: spmd imports jax at module level, and a driver that only
    # builds a JaxTrainer must stay off jax (the chip belongs to its
    # workers; see chip_smoke.py).
    if name in _SPMD_NAMES:
        from ray_tpu.train import spmd
        return getattr(spmd, name)
    raise AttributeError(name)


__all__ = [
    "AsyncCheckpointer", "Checkpoint", "CheckpointConfig",
    "CheckpointManager",
    "ElasticScalingPolicy", "FailureConfig", "FixedScalingPolicy",
    "JaxTrainer", "Result", "RunConfig", "ScalingConfig", "ScalingPolicy",
    "default_optimizer", "get_context", "get_dataset_shard",
    "load_checkpoint_host", "make_train_fns", "profile", "report",
    "restore_checkpoint", "save_checkpoint", "state_shardings",
]
