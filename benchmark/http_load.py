"""The load generator: one thread, one asyncio loop, raw HTTP/1.1 with a
chunked streaming body, as a client of the serve proxy. Every time is
CLOCK_MONOTONIC, which replica and client share on one machine.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import List, Optional, Tuple

from benchmark.traffic import Request


@dataclasses.dataclass
class Outcome:
    index: int
    t_sched: float            # absolute, monotonic
    prompt_tokens: int
    max_tokens: int
    t_send: float = 0.0
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    tokens: int = 0
    chunks: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    ok: bool = False
    abandoned: bool = False   # in flight when a closed loop's window closed
    error: str = ""


async def stream(host: str, port: int, path: str, req: Request,
                  out: Outcome, tokens_out: Optional[List[int]] = None) -> None:
    body = json.dumps({"prompt": req.prompt, "max_tokens": req.max_tokens,
                       "bench_id": req.index}).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        out.t_send = time.monotonic()
        writer.write(
            f"POST {path} HTTP/1.1\r\nHost: {host}\r\nx-serve-stream: 1\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            raise RuntimeError(f"status {status!r}")
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        text = b""
        while True:
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                break
            data = await reader.readexactly(size)
            await reader.readexactly(2)
            now = time.monotonic()
            if out.t_first is None:
                out.t_first = now
            out.t_last = now
            out.chunks.append((now, len(data.split())))
            text += data
        words = text.split()
        if not all(w.isdigit() for w in words):
            raise RuntimeError(f"stream is not integer tokens: {text[:120]!r}")
        out.tokens = len(words)
        if tokens_out is not None:
            tokens_out.extend(int(w) for w in words)
        # Correct in shape: exactly max_tokens integer tokens came back.
        out.ok = out.tokens == req.max_tokens
        if not out.ok:
            out.error = f"{out.tokens} tokens for max_tokens {req.max_tokens}"
    finally:
        writer.close()


async def _guarded(host, port, path, req, out, timeout):
    try:
        await asyncio.wait_for(stream(host, port, path, req, out), timeout)
    except asyncio.CancelledError:
        raise
    except Exception as e:  # a failed request is a result, not a crash
        out.error = out.error or repr(e)[:200]


def run_open(host: str, port: int, path: str, reqs: List[Request],
             seconds: float, drain_s: float) -> Tuple[float, List[Outcome]]:
    """Open loop: each request is sent at its scheduled time whatever the
    server does. Offers load for `seconds`, then lets what is in flight
    finish for at most `drain_s`. Returns (window start, outcomes)."""

    async def main():
        t0 = time.monotonic() + 0.05
        outs = [Outcome(r.index, t0 + r.t_sched, len(r.prompt), r.max_tokens)
                for r in reqs]

        async def one(r, o):
            await asyncio.sleep(max(0.0, o.t_sched - time.monotonic()))
            await _guarded(host, port, path, r, o,
                           (t0 + seconds + drain_s) - time.monotonic())

        await asyncio.gather(*(one(r, o) for r, o in zip(reqs, outs)))
        return t0, outs

    return asyncio.run(main())


def run_closed(host: str, port: int, path: str, reqs: List[Request],
               seconds: float, clients: int) -> Tuple[float, List[Outcome]]:
    """Closed loop: `clients` callers, each sending its next request when its
    last completes, until the window ends; what is in flight then is
    abandoned: neither completed nor failed, but the tokens it streamed
    before the close were work of the window and stay on record."""

    async def main():
        t0 = time.monotonic() + 0.05
        t_end = t0 + seconds
        outs: List[Outcome] = []
        nxt = iter(reqs)

        async def client():
            await asyncio.sleep(max(0.0, t0 - time.monotonic()))
            for r in nxt:
                if time.monotonic() >= t_end:
                    return
                o = Outcome(r.index, time.monotonic(), len(r.prompt),
                            r.max_tokens)
                outs.append(o)
                try:
                    await _guarded(host, port, path, r, o, 600.0)
                except asyncio.CancelledError:
                    o.abandoned = True   # neither completed nor failed
                    raise

        tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
        await asyncio.sleep(max(0.0, t_end - time.monotonic()))
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        return t0, outs

    return asyncio.run(main())
