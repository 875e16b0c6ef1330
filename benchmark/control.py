#!/usr/bin/env python3
"""The control of the comparison that decides `correct`: the plain reference
put in the program's place with its weight matrices rounded to the nearest
type below the one the configuration states (int8 with one scale an output
channel, for bfloat16), judged by the cell's own limits. A check that such a
model passes could not tell a later PR's quantised path from the real one, so
the control has to come out NOT ok. (Rounding through float8_e4m3fn is no
control on a TPU v5e: XLA drops the pair of converts there and the weights
come back unrounded, gaps of exactly 0; my chip run, PR 26.)

    python3 benchmark/control.py --workload serve-batch --seeds 11,12,13

One process on the chip at the cell's own size, no cluster, no timed window,
one JSON line a seed on standard output. The benchmark's runs
never call it; benchmark/tests/test_control.py keeps it at tiny widths.

Serve cells: the control's greedy tokens after each of the check's prompts
(no cache: one forward a token, over a sequence padded to one length so that
one program serves every step, which causal attention allows), then the
float32 reference's gaps at those tokens, as `drivers/serve_common.py`
judges the program's. Beyond the adapter contract this needs the reference's
`logits_last(params, m, tokens, last)`. Train cells: the reference's loss and
gradients on the rounded weights against the same on the real ones, as
`train_loop.py` judges the program's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def rounded(params):
    """Every matrix of the tree rounded to int8 and back, one scale for each
    output channel (the last axis); vectors (norms) stay."""
    import jax
    import jax.numpy as jnp

    def matrix(w):
        w = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.round(w / scale).astype(jnp.int8).astype(jnp.float32) * scale

    @jax.jit
    def one(x):
        if x.ndim < 2:
            return x
        # one matrix of a stacked leaf at a time: its float32 copy is small
        stack = x.reshape((-1,) + x.shape[-2:])
        return jax.lax.map(matrix, stack).astype(x.dtype).reshape(x.shape)

    return jax.tree.map(one, params)


def greedy_by_reference(reference, params, model, prompt: List[int],
                        n: int) -> List[int]:
    """n greedy tokens after `prompt` from the reference's own logits."""
    import jax.numpy as jnp

    seq = list(prompt) + [0] * (n - 1)
    out: List[int] = []
    for i in range(n):
        logits = reference.logits_last(params, model, seq, n)
        out.append(int(jnp.argmax(logits[i])))
        if i + 1 < n:
            seq[len(prompt) + i] = out[-1]
    return out


def serve_control(reference, params, model, prompts, chk) -> Dict:
    from benchmark.drivers.serve_common import judge

    coarse = rounded(params)
    served = [greedy_by_reference(reference, coarse, model, p,
                                  int(chk["tokens"])) for p in prompts]
    del coarse
    return judge([reference.served_token_gaps(params, model, p, s)
                  for p, s in zip(prompts, served)], chk)


def train_control(adapter, params, model, tokens, chk) -> Dict:
    import jax

    from benchmark.train_loop import compare

    both = jax.jit(lambda p: adapter.reference().loss_and_check_grads(
        p, model, tokens))
    (loss, grads), (c_loss, c_grads) = both(params), both(rounded(params))
    rec = compare(adapter.CHECK_LEAVES, float(c_loss), c_grads, float(loss),
                  grads)
    return dict(rec, loss_rel_tol=chk["loss_rel_tol"],
                grad_rel_tol=chk["grad_rel_tol"],
                ok=rec["loss_rel_err"] <= chk["loss_rel_tol"]
                and all(v <= chk["grad_rel_tol"]
                        for v in rec["grad_rel_err"].values()))


def run_cell(cell: str, seeds: List[int], rehearse: bool = False):
    """Yields one record a seed; `ok` is the cell's verdict on its control.
    `rehearse`: the tiny widths of `run.py --rehearse`, for the CPU."""
    import jax.numpy as jnp

    from benchmark import models, traffic
    from benchmark.run import apply_rehearsal, find_cell, load_json

    entry = find_cell(load_json(ROOT, "BENCHMARK.json"), cell)
    config = load_json(ROOT, entry["config_file"])
    scale = apply_rehearsal(HERE, config) if rehearse else 1.0
    mix = load_json(HERE, "traffic", entry["traffic"] + ".json")
    adapter = models.adapter(config["arch"])
    chk = mix["check"]
    cfg = adapter.build_config(config, config["dtypes"],
                               config["deployment"]["max_seq"])
    for seed in seeds:
        params = adapter.init_params(cfg, seed)
        if mix["kind"] == "train":
            n = max(8, int(chk["positions"] * scale))
            tokens = jnp.asarray(traffic.rows(
                mix, int(mix["batch_per_chip"]), seed, config["vocab_size"],
                scale)[:, :n])
            rec = train_control(adapter, params, config, tokens, chk)
        else:
            lengths = [max(2, int(n * scale)) for n in chk["prompt_lengths"]]
            prompts = traffic.sample_prompts(lengths, seed,
                                             config["vocab_size"])
            rec = serve_control(adapter.reference(), params, config, prompts,
                                chk)
        yield dict(rec, cell=cell, seed=seed, control="int8")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU: control flow only")
    args = ap.parse_args()
    import jax

    # The reference builds its programs anew at every call: keep them all.
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    for rec in run_cell(args.workload, [int(s) for s in args.seeds.split(",")],
                        args.rehearse):
        print(json.dumps(dict(rec, platform=dev.platform, kind=dev.device_kind)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
