from ray_tpu.models.llama import (LlamaConfig, forward, init_params,
                                  logical_axes, loss_fn, param_count)

__all__ = ["LlamaConfig", "forward", "init_params", "logical_axes", "loss_fn",
           "param_count"]
