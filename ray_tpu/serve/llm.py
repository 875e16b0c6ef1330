"""LLM serving preset: Llama replicas behind an OpenAI-style endpoint.

Analogue of the reference's LLM layer (reference: python/ray/llm/ —
_internal/serve/deployments/llm/ wraps an engine as Serve deployments with
an OpenAI-compatible router, TP/PP sizes placed via PGs). TPU-native:
the engine IS this framework's Llama; decode runs in jitted device-side
chunks (one host sync per chunk, on the engine's emitter thread; PERF.md
§3 has the latency metrics and where each is measured); replicas are
serve deployments with num_tpus, streamed over the proxy's chunked HTTP
path.

Tokenization is bring-your-own (`LLMConfig.tokenizer` /`detokenizer`
callables); the default passes token-id lists through untouched — there
is no bundled vocabulary (weights here are random unless `params_path`
points at a checkpoint saved by ray_tpu.train).

    import ray_tpu.serve as serve
    from ray_tpu.serve.llm import LLMConfig, build_llm_app

    handle = serve.run(build_llm_app(LLMConfig(d_model=1024, n_layers=8)),
                       name="llm", route_prefix="/v1/completions")
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import ray_tpu.serve as serve
from ray_tpu.utils import get_logger, tracing


@dataclass
class LLMConfig:
    vocab_size: int = 32000
    d_model: int = 1024
    n_layers: int = 8
    max_seq: int = 512
    num_replicas: int = 1
    num_tpus: float = 1
    max_ongoing_requests: int = 16
    decode_chunk: int = 8          # tokens per device call
    page_size: int = 64            # KV page width (tokens)
    kv_pages: Optional[int] = None  # physical pages (None: engine default)
    params_path: str = ""          # ray_tpu.train checkpoint dir (optional)
    tokenizer: Optional[Callable[[str], List[int]]] = None
    detokenizer: Optional[Callable[[List[int]], str]] = None


def _check_engine_health(engine) -> None:
    """Serve's health probe for an engine-backed replica: a dead engine
    loop, or a prefill bucket the compiler refused, makes it unhealthy."""
    fault = engine.error or engine.warm_error
    if fault:
        raise RuntimeError(f"LLM engine unhealthy:\n{fault}")


class LLMServer:
    """The replica: builds the model + continuous-batching engine once
    (XLA compile in the constructor; serve's startup grace covers it),
    then serves streaming completions. Concurrent requests share ONE
    decode loop over a slotted KV arena (serve/engine.py) — aggregate
    tokens/s scales with occupancy instead of serializing."""

    def __init__(self, cfg_blob: bytes):
        import cloudpickle

        from ray_tpu.serve.engine import Engine

        cfg: LLMConfig = cloudpickle.loads(cfg_blob)
        self.cfg = cfg
        self.mcfg, params = _model_from_cfg(cfg)
        self.engine = Engine(params, self.mcfg,
                             n_slots=cfg.max_ongoing_requests,
                             decode_chunk=cfg.decode_chunk,
                             page_size=cfg.page_size,
                             n_pages=cfg.kv_pages)

    def _encode(self, prompt) -> List[int]:
        return _encode_prompt(self.cfg, prompt)

    def _decode_text(self, ids: List[int]):
        if self.cfg.detokenizer is not None:
            return self.cfg.detokenizer(ids)
        return ids

    def check_health(self) -> None:
        _check_engine_health(self.engine)

    def device_info(self) -> Dict[str, Any]:
        """Where this replica runs: the device as JAX reports it, the
        chips the agent pinned, the attention path each traced program
        took, whether each warmed prefill width holds the kernel, and the
        engine's running totals (`Engine.counters()`: read twice, their
        differences are whole-window means, among them the slots a prefill
        stalled, `admit_decoding_slots`, how many requests waited behind an
        admission, `admit_pending`, and how long a freed slot stood empty
        before its next tenant, `slot_idle_s_sum`)."""
        import jax

        from ray_tpu.ops.attention import attention_path_counts

        dev = jax.devices()[0]
        return {
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "pid": os.getpid(),
            "attention_paths": attention_path_counts(),
            "warm_buckets": sorted(self.engine._warm),
            "prefill_has_tpu_custom_call": {
                w: "tpu_custom_call" in self.engine.lowered_prefill_text(w)
                for w in sorted(self.engine._warm)},
            "compile_cache_dir": os.environ.get(
                "JAX_COMPILATION_CACHE_DIR"),
            "engine_counters": self.engine.counters(),
        }

    def __call__(self, body: Dict[str, Any]):
        """Streaming completion: yields decoded chunks (OpenAI-ish
        request body: {"prompt": [...ids] | str, "max_tokens": N,
        "temperature": T, "top_k": K, "seed": S} — temperature 0/absent
        = greedy). Each concurrent request is a slot of the shared
        decode loop."""
        ids = self._encode(body.get("prompt", [1]))
        max_new = int(body.get("max_tokens", 16))
        seed = body.get("seed")
        if seed is None:
            # OpenAI/vLLM semantics: absent seed = fresh entropy per
            # request (a fixed default would make every client's
            # "sampled" completion identical).
            import random as _random
            seed = _random.getrandbits(62)
        stream = self.engine.submit(
            ids, max_new,
            temperature=float(body.get("temperature", 0.0)),
            top_k=int(body.get("top_k", 0)), seed=int(seed))
        while True:
            toks = stream.get()
            if toks is None:
                return
            out = self._decode_text(toks)
            yield (out if isinstance(out, str)
                   else " ".join(str(t) for t in out) + " ")

    def complete(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Non-streaming OpenAI-style response."""
        text = "".join(self(body))
        return {"object": "text_completion",
                "model": f"ray_tpu-llama-{self.cfg.d_model}",
                "choices": [{"index": 0, "text": text,
                             "finish_reason": "length"}]}


def _unflatten(host: Dict[str, Any]) -> Dict[str, Any]:
    """'a.b.c' host-checkpoint keys -> nested dict."""
    out: Dict[str, Any] = {}
    for key, value in host.items():
        parts = key.split(".")
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = value
    return out


def build_llm_app(cfg: LLMConfig):
    """A bound serve application for this LLM config (reference:
    serve/llm build_openai_app)."""
    import cloudpickle

    dep = serve.deployment(
        num_replicas=cfg.num_replicas,
        num_tpus=cfg.num_tpus,
        max_ongoing_requests=cfg.max_ongoing_requests,
    )(LLMServer)
    return dep.bind(cloudpickle.dumps(cfg))


def _model_from_cfg(cfg: "LLMConfig"):
    """(LlamaConfig, device params) — shared by every server flavor."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, init_params

    mcfg = LlamaConfig(
        vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_layers=cfg.n_layers, n_heads=max(2, cfg.d_model // 128),
        n_kv_heads=max(1, cfg.d_model // 256),
        d_ff=int(cfg.d_model * 2.75), max_seq=cfg.max_seq)
    if cfg.params_path:
        from ray_tpu.train.checkpointing import load_checkpoint_host
        host = load_checkpoint_host(cfg.params_path)
        params = jax.tree.map(jnp.asarray, _unflatten(host))
    else:
        params = init_params(mcfg, jax.random.PRNGKey(0))
    # device_put with no target lands on device 0: right only if chip
    # pinning really left this replica the chips it was granted.
    if jax.devices()[0].platform == "tpu" and cfg.num_tpus and \
            jax.device_count() != int(cfg.num_tpus):
        raise RuntimeError(
            f"replica was granted num_tpus={cfg.num_tpus} but sees "
            f"{jax.device_count()} TPU devices (TPU_VISIBLE_CHIPS="
            f"{os.environ.get('TPU_VISIBLE_CHIPS')!r}): chip pinning failed")
    return mcfg, jax.device_put(params)


# ---------------------------------------------------------------------------
# Prefill/decode disaggregation (reference:
# llm/_internal/serve/deployments/prefill_decode_disagg/
# prefill_decode_disagg.py:177 build_pd_openai_app — two engine pools
# joined by a KV-cache transfer backend; here the handoff rides
# DeviceRefs over the transfer plane: DMA within a slice, host-relay
# over DCN across slices).
# ---------------------------------------------------------------------------

def _encode_prompt(cfg: "LLMConfig", prompt) -> List[int]:
    if isinstance(prompt, list):
        return [int(t) for t in prompt]
    if cfg.tokenizer is not None:
        return cfg.tokenizer(prompt)
    raise ValueError(
        "string prompts need LLMConfig.tokenizer; or pass token ids")


class PrefillServer:
    """Prefill pool replica: one full causal pass per prompt, returning
    the first token + the KV cache as DeviceRefs (the tensors stay in
    this replica's HBM until the decode side pulls them)."""

    def __init__(self, cfg_blob: bytes):
        import threading

        import cloudpickle
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.serving import (adopts, prefill_core,
                                            sample_tokens, serving_params)
        from ray_tpu.serve.engine import doubling_widths

        cfg: LLMConfig = cloudpickle.loads(cfg_blob)
        self.cfg = cfg
        self.mcfg, params = _model_from_cfg(cfg)
        if not adopts(self.mcfg):
            raise NotImplementedError(
                "a PD handoff carries K and V, not a sparse-attention "
                "indexer's keys nor a state-space layer's recurrent state "
                "nor latent attention's rows (kv_lora_rank > 0) nor mixed "
                "attention's two caches (attn_pattern: pages and window "
                "rings) nor a short-convolution layer's window (conv_layers)"
                ": this model serves from one engine")
        # The layout the shared prefill core reads, as in the engine.
        self.params = serving_params(params, self.mcfg)
        self._core = jax.jit(prefill_core(self.mcfg))

        def _sample_first(row, temp, topk, key, pos):
            return sample_tokens(row[None], jnp.asarray(temp)[None],
                                 jnp.asarray(topk)[None], key[None],
                                 jnp.asarray(pos)[None])[0]

        self._sample1 = jax.jit(_sample_first)
        # The doubling widths, which are those of the decode side's `adopt`
        # programs (its own prefill ladder is finer in its top octave; this
        # pool still pads to a power of two). Smallest and largest warm
        # eagerly; intermediates warm in the background and requests round
        # UP to a warmed width until then (a synchronous compile inside a
        # request would spike TTFT for everything queued behind it).
        self.buckets: List[int] = doubling_widths(self.mcfg.max_seq)
        self._warm = {self.buckets[0], self.buckets[-1]}

        def warm(width: int) -> None:
            with tracing.compile_span("serve.engine.warm",
                                      program="prefill", width=width):
                out = self._core(self.params,
                                 jnp.zeros((1, width), jnp.int32), 1)
                jax.block_until_ready(out)

        for width in sorted(self._warm):
            warm(width)

        self.warm_error: Optional[str] = None

        def warm_rest():
            for width in self.buckets:
                if width not in self._warm:
                    try:
                        warm(width)
                        self._warm.add(width)
                    except Exception:
                        import traceback
                        self.warm_error = traceback.format_exc()
                        get_logger("serve.llm").error(
                            "prefill bucket %d failed to warm:\n%s",
                            width, self.warm_error)
                        return

        threading.Thread(target=warm_rest, daemon=True,
                         name="prefill-bucket-warm").start()

    def check_health(self) -> None:
        if self.warm_error:
            raise RuntimeError(
                f"prefill bucket warm-up failed:\n{self.warm_error}")

    def prefill(self, body: Dict[str, Any]) -> Dict[str, Any]:
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.device_objects import device_put_ref
        from ray_tpu.serve.engine import _seed_key

        ids = _encode_prompt(self.cfg, body.get("prompt", [1]))
        ids = ids[: self.mcfg.max_seq - 1]
        width = next(b for b in self.buckets
                     if b >= len(ids) and b in self._warm)
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(ids)] = ids
        first, ks, vs, logits_row, _ = self._core(
            self.params, jnp.asarray(toks), len(ids))
        temp = float(body.get("temperature", 0.0))
        if temp > 0:
            # Sample the FIRST token here with the same (seed, position)
            # key derivation as the monolithic engine — identical seeds
            # give identical streams across deployment topologies.
            first = self._sample1(
                logits_row, temp, int(body.get("top_k", 0)),
                jnp.asarray(_seed_key(int(body.get("seed", 0)))),
                len(ids) - 1)
        return {
            "first": int(first),
            "length": len(ids),
            "k": device_put_ref(ks),
            "v": device_put_ref(vs),
        }


class DecodeServer:
    """Decode pool replica: the continuous-batching engine, fed by
    KV handoffs from the prefill pool."""

    def __init__(self, cfg_blob: bytes):
        import cloudpickle

        from ray_tpu.serve.engine import Engine

        cfg: LLMConfig = cloudpickle.loads(cfg_blob)
        self.cfg = cfg
        self.mcfg, params = _model_from_cfg(cfg)
        self.engine = Engine(params, self.mcfg,
                             n_slots=cfg.max_ongoing_requests,
                             decode_chunk=cfg.decode_chunk,
                             page_size=cfg.page_size,
                             n_pages=cfg.kv_pages, adopts=True)

    def check_health(self) -> None:
        _check_engine_health(self.engine)

    def decode_stream(self, meta: Dict[str, Any]):
        """Pull the prefilled KV (device plane; slice-aware) and stream
        the remaining tokens."""
        from ray_tpu.device_objects import device_get, free_ref

        kref, vref = meta["k"], meta["v"]
        ks = device_get(kref, timeout=120.0)
        vs = device_get(vref, timeout=120.0)
        # The prefill side's HBM copy is no longer needed.
        for r in (kref, vref):
            try:
                free_ref(r)
            except Exception:
                pass
        stream = self.engine.submit_prefilled(
            ks, vs, meta["length"], meta["first"], meta["max_tokens"],
            temperature=float(meta.get("temperature", 0.0)),
            top_k=int(meta.get("top_k", 0)),
            seed=int(meta.get("seed", 0)))
        while True:
            toks = stream.get()
            if toks is None:
                return
            yield toks


class PDIngress:
    """Router deployment: prompt -> prefill pool, stream -> decode pool
    (the reference's PDProxyServer shape). The first token streams to
    the client straight from the prefill reply — decode-pool admission
    never sits in front of TTFT."""

    def __init__(self, cfg_blob: bytes, prefill_name: str,
                 decode_name: str):
        import cloudpickle

        self.cfg: LLMConfig = cloudpickle.loads(cfg_blob)
        self._prefill = serve.get_deployment_handle(prefill_name)
        self._decode = serve.get_deployment_handle(decode_name)

    def _decode_text(self, ids: List[int]):
        out = self.cfg.detokenizer(ids) if self.cfg.detokenizer \
            is not None else ids
        return out if isinstance(out, str) \
            else " ".join(str(t) for t in out) + " "

    def __call__(self, body: Dict[str, Any]):
        max_new = int(body.get("max_tokens", 16))
        body = dict(body)
        if body.get("seed") is None:
            # Resolve the seed BEFORE prefill: the prefill side samples
            # the first token with it, the decode side continues with it.
            import random as _random
            body["seed"] = _random.getrandbits(62)
        meta = self._prefill.options(method_name="prefill").remote(
            body).result(timeout=300)
        yield self._decode_text([meta["first"]])
        if max_new <= 1:
            return
        meta["max_tokens"] = max_new
        meta["temperature"] = float(body.get("temperature", 0.0))
        meta["top_k"] = int(body.get("top_k", 0))
        meta["seed"] = int(body["seed"])
        for toks in self._decode.options(
                method_name="decode_stream").stream(meta):
            yield self._decode_text(toks)

    def complete(self, body: Dict[str, Any]) -> Dict[str, Any]:
        text = "".join(self(body))
        return {"object": "text_completion",
                "model": f"ray_tpu-llama-pd-{self.cfg.d_model}",
                "choices": [{"index": 0, "text": text,
                             "finish_reason": "length"}]}


def run_pd_llm_app(cfg: LLMConfig, *, name: str = "llm-pd",
                   num_prefill_replicas: int = 1,
                   num_decode_replicas: int = 1):
    """Deploy the disaggregated app: prefill pool + decode pool +
    ingress; returns the ingress handle (reference:
    prefill_decode_disagg.py:177 build_pd_openai_app)."""
    import cloudpickle

    blob = cloudpickle.dumps(cfg)
    prefill_dep = serve.deployment(
        name=f"{name}-prefill", num_replicas=num_prefill_replicas,
        num_tpus=cfg.num_tpus,
        max_ongoing_requests=cfg.max_ongoing_requests)(PrefillServer)
    decode_dep = serve.deployment(
        name=f"{name}-decode", num_replicas=num_decode_replicas,
        num_tpus=cfg.num_tpus,
        max_ongoing_requests=cfg.max_ongoing_requests)(DecodeServer)
    ingress_dep = serve.deployment(
        name=name, num_replicas=1,
        max_ongoing_requests=4 * cfg.max_ongoing_requests)(PDIngress)
    serve.run(prefill_dep.bind(blob), name=f"{name}-prefill")
    serve.run(decode_dep.bind(blob), name=f"{name}-decode")
    return serve.run(
        ingress_dep.bind(blob, f"{name}-prefill", f"{name}-decode"),
        name=name)
