"""Shared fixtures: the benchmark's harness at a smoke size on the CPU.

These tests are the benchmark's own (the repository's tier-1 suite
collects only ``tests/``).  Run them with

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TESTDATA = ROOT / "bench" / "testdata"


def load(name):
    with open(TESTDATA / name) as f:
        return json.load(f)


def smoke_model_config(config, serve=None):
    """The program's config for the smoke file (no published arch has
    these widths)."""
    import jax.numpy as jnp

    from repro.models.common import ModelConfig

    return ModelConfig(
        name="smoke", family="dense", n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        tie_embeddings=config["tie_word_embeddings"],
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
        attn_backend="pallas",
        peft_backend="pallas" if serve is not None else "reference",
        quant_block_size=64, q_block=32, kv_block=32)


@pytest.fixture
def smoke(monkeypatch):
    """``(config, serve mix, train mix)`` with the harness pointed at the
    smoke model."""
    from bench import serve, train

    monkeypatch.setattr(serve, "model_config", smoke_model_config)
    monkeypatch.setattr(train, "model_config",
                        lambda c: smoke_model_config(c))
    return (load("smoke-config.json"), load("smoke-serve.json"),
            load("smoke-train.json"))


def run_cell(kind, config, traffic, seed=2 ** 40 + 5, seconds=2.0):
    """Drive one cell's whole run (minus the look for a chip)."""
    import jax

    from bench import harness as H, serve, train

    e2e = [{"name": n, "unit": "x"} for n in (
        "ttft_p95_ms", "itl_p95_ms", "train_tokens_per_s", "setup_s",
        "serve_tokens_per_s")]
    cell = H.Cell("smoke", 1, config, traffic, e2e, [])
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    mod = serve if kind == "serve" else train
    return mod.run(cell, args, H.Clock(), jax.devices()[:1], H.Spans(False),
                   {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}, None)
