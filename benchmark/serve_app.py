"""The deployment the serve cells run: `LLMServer` with the model built from a
configuration file.

`serve/llm.py::LLMConfig` derives heads, kv heads and d_ff from d_model and
fixes rope_theta, float32 and PRNGKey(0), so a published model's widths
cannot reach `build_llm_app`. What a Ray Serve user does then is subclass:
this class inherits `__call__`, `complete`, `check_health` and `device_info`
and replaces only the constructor's model building. Proxy, router, replica,
engine, models/ and ops/ are all the program's.

The `bench_*` methods are the harness's side channel (called on the replica
with `handle_request`, outside the measured window): readiness, the
correctness check, the profiler, and the counters the per-layer metrics read.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, Optional

from benchmark import models
from ray_tpu.serve.llm import LLMConfig, LLMServer

COMPILE_EVENTS = ("/jax/compilation_cache/cache_hits",
                  "/jax/compilation_cache/cache_misses")


class _AnnotatedStream:
    """The engine's token queue with its wait named in the profiler trace."""

    def __init__(self, q):
        self._q = q

    def get(self):
        import jax
        with jax.profiler.TraceAnnotation("bench.stream_get"):
            return self._q.get()


class BenchLLMServer(LLMServer):
    def __init__(self, spec_json: str):
        import jax

        from ray_tpu.serve.engine import Engine

        spec = json.loads(spec_json)
        model, eng = spec["model"], spec["engine"]
        adapter = self.adapter = models.adapter(spec["arch"])
        self.model = model
        # The inherited methods read tokenizer, detokenizer and d_model here.
        self.cfg = LLMConfig(
            vocab_size=model["vocab_size"], d_model=model["hidden_size"],
            n_layers=model["num_hidden_layers"], max_seq=eng["max_seq"],
            num_tpus=spec["num_tpus"], max_ongoing_requests=eng["n_slots"],
            decode_chunk=eng["decode_chunk"], page_size=eng["page_size"],
            kv_pages=eng["kv_pages"])
        self._compiles = {e: 0 for e in COMPILE_EVENTS}
        jax.monitoring.register_event_listener(self._on_event)
        dev = jax.devices()[0]
        if spec["num_tpus"] and (dev.platform != "tpu"
                                 or jax.device_count() != spec["num_tpus"]):
            raise RuntimeError(
                f"replica was granted {spec['num_tpus']} TPU chip(s) but sees "
                f"{jax.device_count()} x {dev.platform}")
        self.mcfg = adapter.build_config(model, spec["dtypes"], eng["max_seq"])
        params = adapter.init_params(self.mcfg, spec["seed"])
        self.engine = Engine(params, self.mcfg, n_slots=eng["n_slots"],
                             decode_chunk=eng["decode_chunk"],
                             page_size=eng["page_size"],
                             n_pages=eng["kv_pages"])
        submit = self.engine.submit

        def annotated_submit(*a, **kw):
            with jax.profiler.TraceAnnotation("bench.engine_submit"):
                return _AnnotatedStream(submit(*a, **kw))

        self.engine.submit = annotated_submit
        self._lock = threading.Lock()
        self._stamps: Dict[int, List[float]] = {}
        self._inflight = 0
        self._inflight_peak = 0
        self._compiles_at_mark = dict(self._compiles)

    def _on_event(self, event: str, **_) -> None:
        if event in self._compiles:
            self._compiles[event] += 1

    # -- the request path: inherited, with two stamps around it -----------
    def __call__(self, body: Dict[str, Any]):
        rid = body.get("bench_id")
        t_entry = time.monotonic()
        t_first: Optional[float] = None
        with self._lock:
            self._inflight += 1
            self._inflight_peak = max(self._inflight_peak, self._inflight)
        try:
            for chunk in super().__call__(body):
                if t_first is None:
                    t_first = time.monotonic()
                yield chunk
        finally:
            with self._lock:
                self._inflight -= 1
                if rid is not None and t_first is not None:
                    self._stamps[int(rid)] = [t_entry, t_first,
                                              time.monotonic()]

    # -- the harness's side channel ---------------------------------------
    def bench_ready(self) -> Dict[str, Any]:
        """Ready when every prefill bucket is warm: until then a prompt is
        padded to a wider bucket by accident of timing."""
        warm = sorted(self.engine._warm)
        fault = self.engine.error or self.engine.warm_error
        return {"ready": warm == sorted(self.engine.buckets) and not fault,
                "warm": warm, "buckets": list(self.engine.buckets),
                "fault": fault}

    def bench_check(self, cases: List[Dict[str, List[int]]]) -> List[List[float]]:
        """Per case, the reference's largest logit minus its logit of each
        served token (the adapter's plain reference)."""
        reference = self.adapter.reference()
        return [reference.served_token_gaps(self.engine.params, self.model,
                                            c["prompt"], c["served"])
                for c in cases]

    def bench_mark(self) -> None:
        """The measured window starts: counters from here on."""
        with self._lock:
            self._stamps.clear()
            self._inflight_peak = self._inflight
        self.engine.peak_pages_used = self.engine.pages_in_use()
        self._compiles_at_mark = dict(self._compiles)

    def bench_trace_start(self, out_dir: str) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # host spans come from TraceAnnotation
        jax.profiler.start_trace(out_dir, profiler_options=opts)

    def bench_trace_stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def bench_stats(self) -> Dict[str, Any]:
        import jax
        info = self.device_info()
        mem = jax.local_devices()[0].memory_stats() or {}
        with self._lock:
            stamps = {str(k): v for k, v in self._stamps.items()}
            peak_inflight = self._inflight_peak
        return {
            "platform": info["platform"], "kind": info["device_kind"],
            "count": info["device_count"],
            "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
            "memory_limit_bytes": int(mem.get("bytes_limit", 0)),
            "attention_paths": info["attention_paths"],
            "prefill_has_kernel": info["prefill_has_tpu_custom_call"],
            "compile_cache_dir": info["compile_cache_dir"],
            "compiles_total": dict(self._compiles),
            "compiles_in_window": sum(
                self._compiles[e] - self._compiles_at_mark[e]
                for e in COMPILE_EVENTS),
            "peak_pages_used": int(self.engine.peak_pages_used),
            "n_pages": int(self.engine.n_pages),
            "inflight_peak": peak_inflight,
            "stamps": stamps,
        }
