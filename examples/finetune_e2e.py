"""End-to-end fine-tuning driver: data pipeline -> QuanTA -> train loop ->
async checkpointing -> resume -> eval -> merged export.

    PYTHONPATH=src python examples/finetune_e2e.py [--steps 200] [--big]

Default is a CPU-friendly ~1M-param model; ``--big`` switches to a ~100M
decoder (same code path — the production driver differs only in mesh
setup, see repro/launch)."""

import argparse
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.core.peft import PeftConfig, attach, count_params
from repro.data import SyntheticSeq2Task
from repro.models import build_model
from repro.models.common import ModelConfig
from repro.optim import AdamW, linear_warmup_schedule
from repro.train import TrainState, make_train_step

SMALL = ModelConfig(name="e2e-small", family="dense", n_layers=2,
                    d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                    d_ff=176, vocab_size=256, q_block=32)
BIG = ModelConfig(name="e2e-100m", family="dense", n_layers=8,
                  d_model=768, n_heads=12, n_kv_heads=12, head_dim=64,
                  d_ff=2048, vocab_size=32000, q_block=128)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--big", action="store_true",
                    help="~100M-parameter model")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = BIG if args.big else SMALL
    seq_len = 256 if args.big else 32
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base, peft = attach(
        jax.random.PRNGKey(1), params,
        PeftConfig(method="quanta", n_axes=3, scheme=None),
    )
    print(f"base params: {count_params(base):,}  "
          f"trainable: {count_params(peft):,}")

    opt = AdamW(lr=linear_warmup_schedule(5e-3, args.steps, args.steps // 10))
    state = TrainState.create(base, peft, opt)
    step_fn = jax.jit(make_train_step(model, opt, microbatches=2))

    ckpt_dir = args.ckpt_dir or os.path.join(
        tempfile.gettempdir(), f"quanta_e2e_{cfg.name}"
    )
    ckpt = AsyncCheckpointer(ckpt_dir, keep=2)
    start = 0
    if args.resume and latest_step(ckpt_dir) is not None:
        start = latest_step(ckpt_dir)
        state = restore(ckpt_dir, start, jax.eval_shape(lambda: state))
        print(f"resumed from step {start}")

    data = SyntheticSeq2Task(vocab_size=cfg.vocab_size, seq_len=seq_len,
                             global_batch=16, task_rank=16)
    for i in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        state, metrics = step_fn(state, batch)
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                  f"|g| {float(metrics['grad_norm']):.3f}")
        if i and i % 50 == 0:
            ckpt.save(i, state)
    ckpt.save(args.steps, state)
    ckpt.close()
    print(f"checkpoints in {ckpt_dir}: latest={latest_step(ckpt_dir)}")

    # eval: answer accuracy on held-out batches
    correct = total = 0
    for i in range(10):
        b = data.batch(10_000 + i)
        logits, _ = model.forward(
            state.params, {"tokens": jnp.asarray(b["tokens"])}, state.peft
        )
        labels = np.asarray(b["labels"])
        mask = labels >= 0
        pred = np.asarray(jnp.argmax(logits[..., : cfg.vocab_size], -1))
        correct += int(((pred == labels) & mask).sum())
        total += int(mask.sum())
    print(f"held-out answer accuracy: {correct / max(total, 1):.3f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
