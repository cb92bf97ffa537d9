"""The check's control and planted faults, at a smoke size on the CPU:
each must make ``correct`` come out false.

The harness runs whole (minus the look for a chip) with the timed path
broken underneath it: a served token altered where it is produced; a
training step that returns its state unchanged; a step that leaves half
of the batch out and takes the mean over the rest.  The control puts
the reference, computed in fp8, in the program's place.
"""


import json

import jax
import numpy as np

from conftest import ROOT, load, run_cell


def test_control_fails_serving_limit(smoke):
    from bench import generate, serve

    config, mix, _ = smoke
    front, specs = serve.build_engine(config, 11)
    reqs = generate.serve_requests(mix, config["vocab_size"], 11, len(specs), 2.0)
    serve.warm_up(front, config, mix, specs, _nospans())
    _, _, streams, _ = serve.run_window(front, reqs, specs, 2.0, _nospans())
    sample = serve.sample_finished(streams, 11, 16, 4)
    gaps, ctrl = serve.reference_gaps(config, 11, sample, specs,
                                      precisions=("fp32", "fp8"))
    limit = config["check"]["mean_logit_gap"]
    assert np.concatenate(gaps).mean() <= limit
    assert np.concatenate(ctrl).mean() > limit


def test_control_fails_training_limits(smoke):
    from bench import train

    config, _, mix = smoke
    ref = train.reference_steps(config, mix, 5, 2)
    ctrl = train.reference_steps(config, mix, 5, 2, precision="fp8")
    nums = train.compare(ctrl, ref, config)
    chk = config["check"]
    assert any(nums[k] > chk[k] for k in ("loss_gap", "grad_gap",
                                          "change_gap"))


def test_altered_token_fails(smoke, monkeypatch):
    from repro.serve.engine import ServingEngine

    real = ServingEngine._postprocess

    def altered(self, nxt, active):
        # the third token of each offered request (the warm-up's have
        # uids from 10**9) comes out one higher than it was produced
        nxt = np.array(nxt)
        for i, req in enumerate(self.slots):
            if req is not None and active[i] and req.uid < 10 ** 9 \
                    and len(req.output) == 2:
                nxt[i] = (nxt[i] + 1) % self.cfg.vocab_size
        return real(self, nxt, active)

    monkeypatch.setattr(ServingEngine, "_postprocess", altered)
    config, _, _ = smoke
    # the sample as large as the cell's own: one altered token among
    # some hundreds moves the mean too little, so the widest gap has to
    # catch it
    with open(ROOT / "bench" / "configs" / "minicpm-2b.json") as f:
        cell_check = json.load(f)["check"]
    config["check"].update(min_tokens=cell_check["min_tokens"],
                           max_requests=64)
    result, compared = run_cell("serve", config, load("smoke-offline.json"),
                                seconds=3.0)
    print(compared)
    assert compared["max_logit_gap"]["value"] > \
        config["check"]["max_logit_gap"]
    assert not result["correct"]


def _broken_step(monkeypatch, how):
    import repro.train as rt

    real = rt.make_train_step

    def make(model, opt, **kw):
        step = real(model, opt, **kw)

        def broken(state, batch):
            if how == "unchanged":
                _, met = step(state, batch)
                return state, met
            half = jax.tree_util.tree_map(
                lambda x: x[: x.shape[0] // 2], batch)
            return real(model, opt, microbatches=1)(state, half)

        return broken

    monkeypatch.setattr(rt, "make_train_step", make)


def test_unchanged_state_fails(smoke, monkeypatch):
    _broken_step(monkeypatch, "unchanged")
    config, _, mix = smoke
    result, compared = run_cell("train", config, mix)
    assert compared["change_gap"]["value"] > compared["change_gap"]["limit"]
    assert not result["correct"]


def test_half_batch_fails(smoke, monkeypatch):
    _broken_step(monkeypatch, "half")
    config, _, mix = smoke
    result, _ = run_cell("train", config, mix)
    assert not result["correct"]


def _nospans():
    from bench.harness import Spans

    return Spans(False)
