"""The control of the correctness check (benchmark/control.py) at tiny widths:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_control.py -q

Whether the control FAILS a cell's limits can only be read at the cell's own
size on the chip (`python3 benchmark/control.py --workload <cell> --seeds ...`;
the readings are in PERF.md): a four-layer model of width 128 makes a fortieth
of the rounding noise of twelve layers of width 4096. Here: the rounding does
what it says, the reference judged by itself reads exactly 0, and over the
same prompts the bf16 program reads under the int8 control.
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import control, models, traffic  # noqa: E402

CHK = {"tokens": 4, "logit_tolerance": 0.3, "mean_logit_tolerance": 0.02,
       "loss_rel_tol": 0.001, "grad_rel_tol": 0.08}


@pytest.fixture(scope="module")
def tiny():
    adapter = models.adapter("llama")
    model = dict(adapter.REHEARSE, rope_theta=1e6, rms_norm_eps=1e-5,
                 num_hidden_layers=4, hidden_size=128, head_dim=32,
                 intermediate_size=256, vocab_size=1024)
    cfg = adapter.build_config(model, {"params": "bfloat16",
                                       "activations": "bfloat16"}, 128)
    return adapter, model, cfg


def test_rounding_is_per_output_channel_and_leaves_vectors(tiny):
    import jax.numpy as jnp
    adapter, _, cfg = tiny
    params = adapter.init_params(cfg, 1)
    q = control.rounded(params)
    assert (q["final_norm"] == params["final_norm"]).all()
    w = np.asarray(params["layers"]["wq"][1].astype(jnp.float32))
    r = np.asarray(q["layers"]["wq"][1].astype(jnp.float32))
    assert q["layers"]["wq"].dtype == params["layers"]["wq"].dtype
    # half a step of 1/127 of the column's largest, and bf16's own rounding
    assert 0 < np.abs(r - w).max() <= np.abs(w).max() / 127
    col = r[:, 7] / np.abs(w[:, 7]).max()
    assert len(np.unique(np.round(col * 127))) <= 255


def test_the_program_reads_under_the_control(tiny):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    adapter, model, cfg = tiny
    ref = adapter.reference()
    means = {"reference": [], "program": [], "int8": []}
    for seed in (1, 2):
        params = adapter.init_params(cfg, seed)
        prompts = traffic.sample_prompts([24, 60, 96], seed, 1024)

        def program_greedy(prompt, n):
            seq, out = list(prompt) + [0] * (n - 1), []
            for i in range(n):
                logits = llama.forward(params, jnp.asarray([seq]), cfg)[0]
                out.append(int(jnp.argmax(logits[len(prompt) - 1 + i])))
                if i + 1 < n:
                    seq[len(prompt) + i] = out[-1]
            return out

        for name, served in (
                ("reference", [control.greedy_by_reference(ref, params, model, p, 4)
                               for p in prompts]),
                ("program", [program_greedy(p, 4) for p in prompts])):
            gaps = [g for p, s in zip(prompts, served)
                    for g in ref.served_token_gaps(params, model, p, s)]
            means[name].append(sum(gaps) / len(gaps))
        rec = control.serve_control(ref, params, model, prompts, CHK)
        assert rec["worst_gap"] >= rec["mean_gap"] >= 0.0
        means["int8"].append(rec["mean_gap"])
    assert means["reference"] == [0.0, 0.0]
    total = {k: sum(v) for k, v in means.items()}
    assert total["program"] < total["int8"], means


def test_train_control_reads_the_rounding(tiny):
    import jax.numpy as jnp
    adapter, model, cfg = tiny
    params = adapter.init_params(cfg, 2)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, 1024, (1, 64), dtype=np.int32))
    int8 = control.train_control(adapter, params, model, toks, CHK)
    assert set(int8["grad_rel_err"]) == {"final_norm", "last_attn_norm",
                                         "last_mlp_norm"}
    assert min(int8["grad_rel_err"].values()) > 1e-3 and int8["loss_rel_err"] > 0


@pytest.mark.parametrize("cell", ["serve-batch", "train-1chip"])
def test_a_cells_control_runs_at_rehearsal_size(cell):
    (rec,) = control.run_cell(cell, [5], rehearse=True)
    assert rec["control"] == "int8" and isinstance(rec["ok"], bool)
    assert ("mean_gap" in rec) == cell.startswith("serve")
