#!/usr/bin/env python3
"""Readings that set a cell's limits: the control and the planted faults
at the cell's own size, on several seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

Serving: each seed runs the cell's window at its own load, then the fp32
reference scores the seeded sample of finished requests (the program's
reading) and the fp8 control in the reference's place scores the same
prompts and tokens (the control's reading: the gap of the token the
control puts first); the fault that alters one served token is read from
the same reference logits.  Training: each seed runs the program's
checked steps, the fp32 reference, the fp8 control, and the fault that
leaves half of the batch out.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def serve_seed(cell, seed: int, seconds: float) -> dict:
    import numpy as np

    from bench import generate, harness as H, serve as S

    front, specs = S.build_engine(cell.config, seed)
    reqs = generate.serve_requests(cell.traffic, cell.config["vocab_size"],
                                   seed, len(specs), seconds)
    spans = H.Spans(False)
    S.warm_up(front, cell.config, cell.traffic, specs, spans)
    t0, end, streams, counters = S.run_window(
        front, reqs, specs, seconds, spans,
        finished_tokens=cell.config["check"]["min_tokens"])
    del front
    gc.collect()
    chk = cell.config["check"]
    sample = S.sample_finished(streams, seed, chk["min_tokens"],
                               chk["max_requests"])
    gaps, ctrl, alt = S.reference_gaps(cell.config, seed, sample, specs,
                                       precisions=("fp32", "fp8"),
                                       altered=True)
    g_all, c_all = np.concatenate(gaps), np.concatenate(ctrl)
    return {"seed": seed, "requests": len(sample),
            "tokens": int(len(g_all)),
            "program": float(g_all.max()),
            "control": float(c_all.max()),
            "altered_token": float(min(alt)),
            "program_p99": float(np.percentile(g_all, 99)),
            "control_p99": float(np.percentile(c_all, 99)),
            "program_mean": float(g_all.mean()),
            "control_mean": float(c_all.mean())}


def train_seed(cell, seed: int) -> dict:
    import jax

    from bench import harness as H, train as T

    config, traffic = cell.config, cell.traffic
    n = config["check"]["steps"]
    state, step = T.build(config, seed)
    state, prog = T.first_steps(state, step, traffic, config, seed, n,
                                H.Spans(False))
    jax.block_until_ready(state.peft)
    del state, step
    gc.collect()
    q = config["adapters"]["quanta"]
    ref = T.reference_steps(config, traffic, seed, n)
    ctrl = T.reference_steps(config, traffic, seed, n, precision="fp8")
    half = dict(ref["half"], loss=[ref["half"]["loss"]])
    ref1 = dict(ref, loss=ref["loss"][:1])
    return {"seed": seed,
            "program": T.compare(T.program_readings(prog, q), ref, config),
            "control": T.compare(ctrl, ref, config),
            "half_batch": T.compare(half, ref1, config),
            "ref_loss": ref["loss"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()

    from bench import harness as H

    cell = H.load_cell(args.workload)
    H.require_device(cell.chips)
    H.enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        if cell.traffic["kind"] == "serve":
            out = serve_seed(cell, seed, args.seconds)
        else:
            out = train_seed(cell, seed)
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
