"""Architecture adapter `tinymoe`, a test fixture and no public model: the
llama block with a sparse SwiGLU feed-forward, which the program already
trains (`LlamaConfig(n_experts=E, top_k_experts=k)` through ops/moe.py). It
stands for what every later architecture brings: an adapter, a reference, a
counts file and rehearsal widths, all new files. The contract is
benchmark/models/llama.py's.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_tinymoe as counts  # noqa: F401
from benchmark.models import llama as dense

CHECK_LEAVES = dict(dense.CHECK_LEAVES, router=("layers", "router"))

REHEARSE = dict(dense.REHEARSE, intermediate_size=64, num_experts=4,
                num_experts_per_tok=2)


def check_supported(model: Dict[str, Any]) -> None:
    dense.check_supported(model)
    if model.get("norm_topk_prob") is False:
        raise ValueError("arch 'tinymoe' normalises the router's weights over "
                         "the selected experts (ops/moe.py)")


def build_config(model: Dict[str, Any], dtypes: Dict[str, str], max_seq: int):
    from ray_tpu.models.llama import LlamaConfig
    check_supported(model)
    return LlamaConfig(n_experts=model["num_experts"],
                       top_k_experts=model["num_experts_per_tok"],
                       **dense.to_model_kwargs(model, dtypes, max_seq))


init_params = dense.init_params
loss_fn = dense.loss_fn


def reference():
    from benchmark import reference_tinymoe
    return reference_tinymoe
