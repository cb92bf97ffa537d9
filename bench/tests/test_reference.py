"""The plain reference against the program at a smoke size on the CPU,
and the harness's refusal to report from a CPU."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from conftest import ROOT, load, run_cell, smoke_model_config


def test_serving_matches_reference(smoke):
    config, serve_mix, _ = smoke
    result, compared = run_cell("serve", config, serve_mix)
    for name in ("mean_logit_gap", "max_logit_gap"):
        assert compared[name]["value"] <= config["check"][name], name
    assert result["correct"]


def test_training_matches_reference(smoke):
    config, _, train_mix = smoke
    result, compared = run_cell("train", config, train_mix)
    for name in ("loss_gap", "grad_gap", "change_gap"):
        assert compared[name]["value"] <= compared[name]["limit"], name
    assert compared["frozen_change"]["value"] == 0.0
    assert result["correct"]


def test_reference_logits_match_program_forward(smoke):
    """Full-sequence logits of the program's bf16 model against the fp32
    reference on the same seeded weights: close, by a bf16 margin."""
    from bench import weights as W
    from bench.reference import dense as R
    from repro.models import build_model

    config, _, _ = smoke
    key = W.root_key(3)
    cfg = smoke_model_config(config).replace(attn_backend="reference")
    params = jax.jit(lambda: W.base_params(config, key))()
    toks = np.random.default_rng(0).integers(0, config["vocab_size"], 40)
    logits, _ = build_model(cfg).forward(params, {"tokens": jnp.asarray(
        toks[None], jnp.int32)})
    hid = R.hidden(config, key, jnp.asarray(toks), lambda l, s: {}, None,
                   nf4=False)
    ref = R.logits_at(config, key, hid, 0, 40)
    got = np.asarray(logits[0, :, :config["vocab_size"]], np.float32)
    scale = float(np.abs(ref).max())
    assert float(np.abs(got - ref).max()) < 0.05 * scale


def test_nf4_roundtrip_matches_program():
    from bench.reference import dense as R
    from repro.core.quantize import dequantize, quantize_linear

    w = jax.random.normal(jax.random.PRNGKey(0), (256, 96),
                          jnp.float32).astype(jnp.bfloat16)
    got = dequantize(quantize_linear(w, "nf4", block_size=64), jnp.float32)
    np.testing.assert_allclose(np.asarray(R.nf4_roundtrip(w)),
                               np.asarray(got), rtol=0, atol=1e-6)


def test_quanta_shapes_match_program(smoke):
    """The tensor shapes the config states are the program's."""
    from bench import serve, weights as W
    from bench.train import peft_config

    config, _, _ = smoke
    q = config["adapters"]["quanta"]
    tmpl = serve.adapter_template(config, peft_config(config), q["targets"])
    for tgt, t in q["targets"].items():
        ad = tmpl.tree["layers"]["attn"][tgt]
        assert [tuple(x.shape[1:]) for x in ad.tensors] == \
            W.quanta_shapes(t["dims_in"], t["dims_out"], q["pairs"])
        assert tuple(ad.pairs) == tuple(map(tuple, q["pairs"]))


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "minicpm-2b.serve.tenants-sat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_offline_batch_opens_with_full_slots(smoke):
    """An offline mix queues every request at once; the window opens
    once the engine holds all it can, and only started requests count."""
    config, _, _ = smoke
    mix = load("smoke-offline.json")
    result, compared = run_cell("serve", config, mix, seconds=1.0)
    assert result["correct"]
    assert config["serve"]["n_slots"] <= result["attempted"] < \
        mix["requests"]
    assert result["metrics"]["itl_p95_ms"]["value"] > 0
