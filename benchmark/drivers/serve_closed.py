"""Closed loop: the mix's number of clients, each sending its next request
when its last completes, until the window ends."""

from __future__ import annotations

from typing import Any, Dict

from benchmark import http_load
from benchmark.drivers import serve_common


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    clients = int(ctx["traffic"]["arrivals"]["clients"])
    return serve_common.run(
        ctx, lambda host, port, path, reqs: http_load.run_closed(
            host, port, path, reqs, ctx["seconds"], clients))
