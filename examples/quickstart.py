"""Quickstart: QuanTA fine-tuning in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py

1. build a small decoder (the llama2-like smoke config),
2. attach QuanTA to q_proj/v_proj (zero-init via the frozen-copy fold),
3. fine-tune 40 steps on a synthetic task — only the tensors train,
4. merge the trained operator into the weights: the deployed model needs
   NO adapter code and matches the adapted model exactly (paper §6).
"""

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.core.peft import PeftConfig, attach, merge_all, trainable_fraction
from repro.data import SyntheticSeq2Task
from repro.models import build_model
from repro.optim import AdamW
from repro.train import TrainState, make_train_step


def main():
    cfg = get_smoke("llama2-7b-proxy")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    peft_cfg = PeftConfig(method="quanta", n_axes=3, scheme=None)
    base, peft = attach(jax.random.PRNGKey(1), params, peft_cfg)
    print(f"trainable: {trainable_fraction(base, peft):.3f}% of parameters")

    opt = AdamW(lr=5e-3)
    state = TrainState.create(base, peft, opt)
    step = jax.jit(make_train_step(model, opt))
    data = SyntheticSeq2Task(vocab_size=cfg.vocab_size, seq_len=32,
                             global_batch=16, task_rank=8)
    for i in range(40):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        state, metrics = step(state, batch)
        if i % 10 == 0:
            print(f"step {i:3d}  loss {float(metrics['loss']):.4f}")

    merged = merge_all(state.params, state.peft)
    batch = {k: jnp.asarray(v) for k, v in data.batch(999).items()}
    la, _ = model.forward(state.params, batch, state.peft)
    lm, _ = model.forward(merged, batch, None)
    err = float(jnp.max(jnp.abs(la - lm)))
    print(f"merged-vs-adapted max |logit diff| = {err:.2e}  "
          f"(zero inference overhead)")
    assert err < 1e-3


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
