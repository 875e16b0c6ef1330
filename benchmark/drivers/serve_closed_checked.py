"""`serve_closed`, after asking the configuration's adapter, here in the
parent process and before any cluster starts, whether this program can
express the model (`build_config`, which names the field that is missing).
A program that cannot fails here in a second with exit code 1. Left to the
replica's constructor the same error is raised in an actor that the Serve
controller restarts for ever, and the run hangs (the parent of PR 27 on
`serve-batch-olmoe`: 10 minutes until killed, my CPU rehearsal, PR 27).
Building the config imports jax and initialises no backend."""

from __future__ import annotations

from typing import Any, Dict

from benchmark import models
from benchmark.drivers import serve_closed


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    config = ctx["config"]
    models.adapter(config["arch"]).build_config(
        config, config["dtypes"], config["deployment"]["max_seq"])
    return serve_closed.run(ctx)
