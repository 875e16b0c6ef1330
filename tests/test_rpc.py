"""Unit tests for the RPC layer: dedup of retried non-idempotent calls,
chaos injection, and backoff retry (reference analogues:
src/ray/rpc/retryable_grpc_client.cc, rpc_chaos.cc)."""

import asyncio

import pytest

from ray_tpu.core.rpc import RpcClient, RpcServer
from ray_tpu.utils.config import GlobalConfig


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def test_distinct_calls_not_deduped():
    """Two separate logical calls carry distinct request ids and both
    execute (dedup must never collapse different calls)."""

    calls = {"n": 0}

    class Svc:
        async def bump(self):
            calls["n"] += 1
            return calls["n"]

    async def main():
        srv = RpcServer("t")
        srv.register_object(Svc())
        port = await srv.start_tcp("127.0.0.1", 0)
        client = RpcClient(("127.0.0.1", port), max_retries=5)
        # Simulate lost replies: execute directly through the dedup path
        # twice with the same rid, as a retry would.
        out1 = await client.call("bump")
        out2 = await client.call("bump")
        assert (out1, out2) == (1, 2)  # distinct calls still distinct
        await client.close()
        await srv.stop()

    run(main())


def test_retry_dedup_replays_same_rid():
    calls = {"n": 0}

    class Svc:
        async def bump(self):
            calls["n"] += 1
            return calls["n"]

    async def main():
        srv = RpcServer("t")
        srv.register_object(Svc())
        port = await srv.start_tcp("127.0.0.1", 0)
        client = RpcClient(("127.0.0.1", port), max_retries=5)
        # Force the same request id across two wire sends by driving the
        # internals: first real call to learn the rid scheme, then re-send.
        client._rid_counter = 100
        out1 = await client.call("bump")
        rid = f"{client._rid_prefix}:{client._rid_counter}"
        # Re-send the identical request id directly.
        from ray_tpu.core.rpc import _write_msg
        import pickle
        client._seqno += 1
        seqno = client._seqno
        fut = asyncio.get_running_loop().create_future()
        client._pending[seqno] = fut
        _write_msg(client._writer,
                   [seqno, "bump", pickle.dumps(((), {}), protocol=5), rid])
        await client._writer.drain()
        out2 = await fut
        assert out1 == out2 == 1, "duplicate rid must replay, not re-execute"
        assert calls["n"] == 1
        await client.close()
        await srv.stop()

    run(main())


def test_chaos_injection_retries_through():
    class Svc:
        async def hello(self):
            return "hi"

    async def main():
        srv = RpcServer("t")
        srv.register_object(Svc())
        port = await srv.start_tcp("127.0.0.1", 0)
        GlobalConfig.testing_rpc_failure = "hello=0.5"
        try:
            client = RpcClient(("127.0.0.1", port), max_retries=20)
            for _ in range(10):
                assert await client.call("hello") == "hi"
            await client.close()
        finally:
            GlobalConfig.testing_rpc_failure = ""
        await srv.stop()

    run(main())


def test_stop_drops_established_connections_and_returns():
    """A peer that keeps its socket open must not hold `stop()`: since
    Python 3.12 `Server.wait_closed()` waits for every connection, so the
    established ones are dropped first. The peer sees the loss: its call
    in flight fails instead of waiting for a reply that cannot come."""

    class Svc:
        async def ping(self):
            return "pong"

        async def park(self):
            await asyncio.sleep(60)

    async def main():
        srv = RpcServer("t")
        srv.register_object(Svc())
        port = await srv.start_tcp("127.0.0.1", 0)
        client = RpcClient(("127.0.0.1", port), timeout=30.0, max_retries=0)
        assert await client.call("ping") == "pong"   # connection is up
        parked = asyncio.ensure_future(client.call("park"))
        await asyncio.sleep(0.1)
        await asyncio.wait_for(srv.stop(), 5.0)
        with pytest.raises(Exception):
            await asyncio.wait_for(parked, 5.0)
        await client.close()

    run(main())
