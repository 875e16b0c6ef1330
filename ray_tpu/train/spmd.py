"""SPMD training step: sharded init + jitted train step over a ParallelContext.

This is the per-worker compute path that ray_tpu.train's JaxTrainer workers
run (the analogue of the user's train_loop_per_worker in the reference,
python/ray/train/v2/jax/jax_trainer.py:19 — but here the framework owns the
sharded step, optimizer-state sharding, and donation).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.parallel.context import ParallelContext
from ray_tpu.parallel.sharding import tree_shardings
from ray_tpu.utils import tracing

TrainState = Dict[str, Any]  # {"params", "opt_state", "step"}


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def state_shardings(cfg: llama.LlamaConfig, ctx: ParallelContext,
                    opt: optax.GradientTransformation) -> TrainState:
    param_sh = tree_shardings(llama.logical_axes(cfg), ctx.mesh, ctx.rules)
    replicated = NamedSharding(ctx.mesh, P())
    opt_shapes = jax.eval_shape(
        lambda: opt.init(llama.init_params(cfg, jax.random.PRNGKey(0))))
    opt_sh = optax.tree_map_params(
        opt, lambda _, s: s, opt_shapes, param_sh,
        transform_non_params=lambda _: replicated)
    return {"params": param_sh, "opt_state": opt_sh, "step": replicated}


class _Traced:
    """A jitted function with spans around its dispatch: `train.compile`
    around the first call, which traces and compiles, and, for the step,
    `train.step` around each later one (a step to XProf too). Everything
    else (`lower`, `trace`, ...) is the jitted function's own."""

    def __init__(self, jitted: Callable, program: str):
        self._jitted, self._program, self._calls = jitted, program, 0

    def __call__(self, *args):
        n, self._calls = self._calls, self._calls + 1
        if n == 0:
            around = tracing.compile_span("train.compile",
                                          program=self._program)
        elif self._program == "step":
            around = tracing.span("train.step", step_num=n)
        else:
            around = contextlib.nullcontext()
        with around:
            return self._jitted(*args)

    def __getattr__(self, name: str):
        return getattr(self._jitted, name)


def make_train_fns(cfg: llama.LlamaConfig, ctx: ParallelContext,
                   opt: Optional[optax.GradientTransformation] = None,
                   loss_fn: Optional[Callable] = None,
                   ) -> Tuple[Callable[[jax.Array], TrainState],
                              Callable[[TrainState, jax.Array],
                                       Tuple[TrainState, Dict[str, jax.Array]]]]:
    """Returns (init_fn(key) -> state, step_fn(state, tokens) -> (state, metrics)),
    both jitted with explicit shardings; step donates the state."""
    opt = opt or default_optimizer()
    loss = loss_fn or (lambda p, toks: llama.loss_fn(p, toks, cfg, ctx))
    shardings = state_shardings(cfg, ctx, opt)
    batch_sh = ctx.batch_sharding()

    def init_fn(key: jax.Array) -> TrainState:
        params = llama.init_params(cfg, key)
        return {"params": params, "opt_state": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    def step_fn(state: TrainState, tokens: jax.Array):
        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(
            state["params"], tokens)
        with jax.named_scope("optimizer"):   # clip + AdamW + apply
            updates, new_opt = opt.update(grads, state["opt_state"],
                                          state["params"])
            new_params = optax.apply_updates(state["params"], updates)
            gnorm = optax.global_norm(grads)
        metrics = dict(metrics, grad_norm=gnorm)
        return ({"params": new_params, "opt_state": new_opt,
                 "step": state["step"] + 1}, metrics)

    init_jit = jax.jit(init_fn, out_shardings=shardings)
    step_jit = jax.jit(step_fn,
                       in_shardings=(shardings, batch_sh),
                       out_shardings=(shardings, None),
                       donate_argnums=(0,))
    return _Traced(init_jit, "init"), _Traced(step_jit, "step")
