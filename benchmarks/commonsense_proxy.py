"""Paper Table 3/4 proxy: multi-task fine-tuning (commonsense/arithmetic
stand-in).

The paper fine-tunes ONE model on a task mixture (COMMONSENSE170K /
MATH10K) and evaluates per-task.  Here: a mixture of three planted-rank
teachers (low / mid / high) distilled jointly into a single adapter; the
per-task agreement + average is the Table-3-style report.  QuanTA's claim:
one high-rank-capable adapter handles the mixed-rank mixture, while LoRA's
budget is consumed by the high-rank component."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import (
    ATTACH_SEED, DistillLoss, csv_row, make_task,
    _accuracy,
)
from repro.core.peft import PeftConfig, attach, count_params
from repro.optim import AdamW
from repro.train import TrainState, make_train_step

TASK_KINDS = {"taskA_low": "low", "taskB_mid": "mid", "taskC_high": "high"}


def _mix_batch(tasks, step):
    parts = [t.batch(step) for t in tasks.values()]
    return {
        k: jnp.concatenate([p[k] for p in parts], axis=0) for k in parts[0]
    }


def main(steps: int = 300) -> dict:
    tasks = {n: make_task(kind, seed=i)
             for i, (n, kind) in enumerate(TASK_KINDS.items())}
    any_task = next(iter(tasks.values()))
    model = any_task.model
    results = {}
    for method, kw in [("lora", dict(rank=8)), ("quanta", dict(n_axes=3))]:
        pc = PeftConfig(method=method, scheme=None, **kw)
        base, peft = attach(
            jax.random.PRNGKey(ATTACH_SEED + 1), any_task.base_params, pc
        )
        opt = AdamW(lr=5e-3)
        state = TrainState.create(base, peft, opt)
        step_fn = jax.jit(make_train_step(DistillLoss(model), opt))
        t0 = time.time()
        for i in range(steps):
            state, _ = step_fn(state, _mix_batch(tasks, i))
        accs = {
            name: _accuracy(model, state.params, state.peft, task, steps)
            for name, task in tasks.items()
        }
        avg = sum(accs.values()) / len(accs)
        results[method] = dict(accs=accs, avg=avg)
        print(csv_row(
            f"commonsense_proxy/{method}",
            1e6 * (time.time() - t0) / steps,
            ";".join(f"{k}={v:.3f}" for k, v in accs.items())
            + f";avg={avg:.3f};params={count_params(peft)}",
        ))
    assert results["quanta"]["avg"] > results["lora"]["avg"] - 0.05
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
