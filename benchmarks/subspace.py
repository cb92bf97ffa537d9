"""Paper Fig. 2 / App. A reproduction: subspace-similarity "intrinsic rank"
diagnostic.

Trains LoRA at two ranks (4 and 8) on the low- and high-intrinsic-rank
teachers, then compares the right-singular subspaces of the two resulting
q_proj updates (App. A Eq. A.1).  Paper signature reproduced here:

* low-rank task: the first ``planted_rank`` directions agree almost
  perfectly between the two runs (phi ~ 1) and similarity DECAYS once i
  exceeds the intrinsic rank (the extra directions are noise),
* high-rank task: similarity stays flat(ter) out to large i — every
  direction carries task signal (the "DROP" regime).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import csv_row, finetune, make_task
from repro.core.analysis import similarity_grid

GRID = 8


def _lora_update(res):
    """Materialize the trained q_proj LoRA update of layer 0."""
    ad = res.peft_state["layers"]["attn"]["q_proj"]
    a = np.asarray(ad.a[0])
    b = np.asarray(ad.b[0])
    return (ad.alpha / a.shape[1]) * (a @ b)


def main(steps: int = 300) -> dict:
    out = {}
    t0 = time.time()
    for task_name, kind in [("low_rank", "low"), ("high_rank", "high")]:
        task = make_task(kind)
        r1 = finetune("lora", task, steps=steps, rank=4, keep_state=True)
        r2 = finetune("lora", task, steps=steps, rank=8, keep_state=True,
                      seed=11)
        dw1, dw2 = _lora_update(r1), _lora_update(r2)
        grid = similarity_grid(dw1, dw2, GRID, GRID)
        pr = task.planted_rank
        head = float(grid[min(pr, GRID) - 1, min(pr, GRID) - 1])
        tail = float(grid[GRID - 1, GRID - 1])
        out[task_name] = dict(planted_rank=pr, phi_head=head, phi_tail=tail,
                              decay=head - tail)
        print(csv_row(
            f"subspace/{task_name}",
            1e6 * (time.time() - t0) / steps,
            f"planted_rank={pr};phi(r,r)={head:.3f};"
            f"phi({GRID},{GRID})={tail:.3f};decay={head - tail:.3f}",
        ))
    # Fig. 2 signature: beyond the intrinsic rank, similarity decays on the
    # low-rank task; relative decay is milder on the high-rank task.
    low, high = out["low_rank"], out["high_rank"]
    assert low["phi_head"] > 0.85, out
    assert low["decay"] > high["decay"] - 0.05, out
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
