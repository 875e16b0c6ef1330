"""Serve benchmark: p50/p95 TTFT + decode throughput for the LLM app
(continuous-batching engine) behind the HTTP proxy, plus a concurrency
sweep showing aggregate tokens/s scaling with in-flight streams.

The reference ships no TTFT baseline (BASELINE.json published: {}); this
produces the framework's own numbers (driver metadata north star: Serve
p50 TTFT through controller -> proxy -> pow-2 router -> replica actor;
continuous-batching parity target: aggregate tokens/s scaling like
vLLM's batcher, reference: llm/_internal/serve/.../vllm_models.py:170).

Run: python bench_serve.py [--quick]
Prints one JSON line per metric.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.request

QUICK = "--quick" in sys.argv
TTFT_ONLY = "--ttft-only" in sys.argv  # solo TTFT + decode rate, no sweep
PD = "--pd" in sys.argv  # disaggregated prefill/decode pools instead of
# the monolithic engine (reference: prefill_decode_disagg.py)


def emit(metric: str, value: float, unit: str) -> None:
    print(json.dumps({"metric": metric, "value": round(value, 2),
                      "unit": unit}), flush=True)


def main() -> None:
    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu import accelerators
    from ray_tpu.serve.llm import LLMConfig, build_llm_app

    # Replicas take their chips from the scheduler, which takes them from
    # what the node agent detects on this host.
    chips = accelerators.num_tpu_chips()
    need = 2 if PD else 1  # PD: one chip PER POOL (a chip is process-exclusive)
    if chips < need:
        sys.exit(f"bench_serve.py{' --pd' if PD else ''} needs {need} TPU "
                 f"chip(s) on this host and found {chips}; it does not "
                 f"measure a CPU")
    ray_tpu.init(resources={"CPU": 8})
    try:
        serve.start(http=True)
        cfg = LLMConfig(
            vocab_size=32000,
            d_model=512 if QUICK else 1024,
            n_layers=4 if QUICK else 8,
            max_seq=256,
            num_tpus=1,
            max_ongoing_requests=16,  # decode-loop slots (paged KV)
            decode_chunk=8,
            page_size=64)
        if PD:
            from ray_tpu.serve.llm import run_pd_llm_app
            run_pd_llm_app(cfg, name="llama")
        else:
            serve.run(build_llm_app(cfg), name="llama")
        port = serve.get_proxy().port
        url = f"http://127.0.0.1:{port}/llama"

        def one_request(max_tokens: int = 8) -> tuple:
            req = urllib.request.Request(
                url, data=json.dumps(
                    {"prompt": list(range(1, 17)),
                     "max_tokens": max_tokens}).encode(),
                headers={"x-serve-stream": "1"})
            t0 = time.perf_counter()
            ttft = None
            n_tok = 0
            body = b""
            with urllib.request.urlopen(req, timeout=600) as resp:
                # read(1): http.client's chunked read(n) waits to gather n
                # bytes ACROSS chunks, which would hide first-chunk timing.
                while True:
                    chunk = resp.read(1)
                    if not chunk:
                        break
                    if ttft is None:
                        ttft = time.perf_counter() - t0
                    body += chunk
                    n_tok += chunk == b" "
            total = time.perf_counter() - t0
            # Guard against measuring an error payload as a "fast token".
            first = body.split()[0] if body.split() else b""
            if not first.isdigit():
                raise RuntimeError(f"bad stream payload: {body[:200]!r}")
            return ttft, n_tok, total

        one_request()  # warmup through the full stack
        n = 5 if QUICK else 15
        ttfts, rates = [], []
        for _ in range(n):
            ttft, n_tok, total = one_request()
            ttfts.append(ttft * 1000)
        # Solo decode rate over a LONG stream (the pipelined engine
        # delivers a short request's tokens in ~one chunk, which would
        # measure emit burstiness, not decode speed).
        for _ in range(2):
            ttft, n_tok, total = one_request(max_tokens=96)
            if total > ttft and n_tok > 1:
                rates.append((n_tok - 1) / (total - ttft))
        ttfts.sort()
        solo_p50 = ttfts[len(ttfts) // 2]
        emit("serve_llama_ttft_p50", solo_p50, "ms")
        emit("serve_llama_ttft_p95",
             ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.95))], "ms")
        if rates:
            emit("serve_llama_decode_tokens_per_s",
                 sum(rates) / len(rates), "tokens/s")

        # Aggregate decode throughput at 8 concurrent streams (the paged
        # engine's density metric; target >=120 tokens/s = 10x the r4
        # slotted-arena number). Runs in TTFT_ONLY mode too so bench.py
        # records it every round.
        agg_tokens = 32
        conc0 = 8
        agg_results: list = [None] * conc0
        agg_errors: list = []

        def agg_run(i):
            try:
                agg_results[i] = one_request(agg_tokens)
            except Exception as e:
                agg_errors.append((i, repr(e)))

        t0 = time.perf_counter()
        agg_threads = [threading.Thread(target=agg_run, args=(i,))
                       for i in range(conc0)]
        for t in agg_threads:
            t.start()
        for t in agg_threads:
            t.join()
        agg_wall = time.perf_counter() - t0
        if agg_errors:
            raise RuntimeError(
                f"{len(agg_errors)} of {conc0} concurrent request(s) "
                f"failed: {agg_errors[:2]!r}")
        emit("serve_llama_decode_agg_tokens_per_s",
             sum(r[1] for r in agg_results) / agg_wall, "tokens/s")
        if TTFT_ONLY:
            return

        # ------------------------------------------------------------------
        # Concurrency sweep: aggregate tokens/s + p50 TTFT per level.
        # Continuous batching target: >=4x aggregate 1 -> 8 streams, TTFT
        # p50 within 2x of solo.
        # ------------------------------------------------------------------
        max_tokens = 16 if QUICK else 32
        base_rate = None
        for conc in (1, 4, 8):
            results: list = [None] * conc
            errors: list = []

            def run(i):
                try:
                    results[i] = one_request(max_tokens)
                except Exception as e:  # surfaced below, not swallowed
                    errors.append((i, repr(e)))

            t0 = time.perf_counter()
            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(conc)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                raise RuntimeError(
                    f"concurrency level {conc}: {len(errors)} request(s) "
                    f"failed: {errors}")
            toks = sum(r[1] for r in results)
            c_ttfts = sorted(r[0] * 1000 for r in results)
            agg = toks / wall
            p50 = c_ttfts[len(c_ttfts) // 2]
            emit(f"serve_llama_agg_tokens_per_s_c{conc}", agg, "tokens/s")
            emit(f"serve_llama_ttft_p50_c{conc}", p50, "ms")
            if conc == 1:
                base_rate = agg
            elif conc == 8 and base_rate:
                emit("serve_llama_batching_speedup_1_to_8",
                     agg / base_rate, "x")
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


if __name__ == "__main__":
    main()
