"""Training cells: the jitted ``make_train_step`` step of fold-free
QuanTA on a frozen bf16 base with AdamW, on packed documents.

Set-up builds one object, the compiled step with its state, and drives
it from the seed through its first steps on rows that all differ; the
window then runs the same object on fresh rows for ``--seconds`` and
counts whole steps.  The first steps are what the reference follows:
each step's loss, the first gradient as the optimizer got it (read from
Adam's first moment after step 1), and each leaf's change over the
checked steps, read before the next step overwrites it.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate, weights as W
from bench.reference import dense as R


def model_config(config: dict):
    from repro.configs import get_config

    from bench.serve import check_widths

    cfg = get_config(config["arch"]).replace(
        n_layers=config["num_hidden_layers"], attn_backend="pallas",
        peft_backend="reference")
    check_widths(cfg, config)
    return cfg


def peft_config(config: dict):
    from repro.core.peft import PeftConfig

    q = config["adapters"]["quanta"]
    return PeftConfig(method="quanta", n_axes=len(q["pairs"]),
                      scheme=q["scheme"], fold=False,
                      targets=tuple(rf".*/{t}$" for t in q["targets"]))


def initial_adapters(config: dict, key):
    """The run's adapters at step 0 (``T == S``), in the program's
    ``AdapterSet`` layout."""
    from bench.serve import adapter_template, fill_template

    q = config["adapters"]["quanta"]
    tmpl = adapter_template(config, peft_config(config), q["targets"])
    arrays = jax.jit(lambda k: W.quanta_stacked(
        config, k, q, jnp.arange(1) + q["train_tenant"], trained=False))(key)
    return fill_template(tmpl, arrays, 0)


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.device_get([jnp.linalg.norm(x.astype(jnp.float32))
                                      for x in jax.tree_util.tree_leaves(tree)]))


def build(config: dict, seed: int):
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.train import TrainState, make_train_step

    tr = config["train"]
    key = W.root_key(seed)
    model = build_model(model_config(config))
    params = jax.jit(lambda k: W.base_params(config, k))(key)
    peft = initial_adapters(config, key)
    opt = AdamW(lr=tr["lr"], b1=tr["b1"], b2=tr["b2"], eps=tr["eps"],
                weight_decay=0.0, max_grad_norm=tr["max_grad_norm"])
    state = TrainState.create(params, peft, opt)
    step = jax.jit(make_train_step(model, opt,
                                   microbatches=tr["microbatches"]),
                   donate_argnums=(0,))
    return state, step


def batch_of(traffic, vocab, seed, i):
    rows = generate.train_rows(traffic, vocab, seed, i)
    return {"tokens": jnp.asarray(rows[:, :-1]),
            "labels": jnp.asarray(rows[:, 1:])}


def first_steps(state, step, traffic, config, seed, n_check: int, spans):
    """Run the first ``n_check + 1`` steps through the window's own
    call: losses of the checked steps, per-leaf norms of the first
    gradient (Adam's first moment after step 1 over ``1 - b1``) and of
    each leaf's change after ``n_check`` steps."""
    b1 = config["train"]["b1"]
    vocab = config["vocab_size"]
    p0 = jax.tree_util.tree_map(jnp.copy, state.peft)
    losses = []
    for i in range(n_check):
        with spans("bench.step"):
            state, met = step(state, batch_of(traffic, vocab, seed, i))
        losses.append(float(met["loss"]))
        if i == 0:
            g1 = leaf_norms(state.opt_state.mu) / (1.0 - b1)
    change = leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b,
                                               state.peft, p0))
    names = leaf_names(state.peft)
    del p0
    return state, {"loss": losses, "grad": g1, "change": change,
                   "names": names}


def run_window(state, step, traffic, config, seed, start, seconds, spans,
               trace_dir=None, trace_steps=(0, 0)):
    """Whole steps until ``seconds`` have passed; the rate is the tokens
    of those steps over the time they took."""
    vocab = config["vocab_size"]
    tokens = traffic["step_tokens"]
    counters = {"steps": 0}
    nxt = batch_of(traffic, vocab, seed, start)
    t0 = time.perf_counter()
    i = start
    tracing = False
    while True:
        if trace_dir is not None and counters["steps"] == trace_steps[0]:
            jax.block_until_ready(state.peft)
            jax.profiler.start_trace(trace_dir)
            tracing, tt0 = True, time.perf_counter()
            span_cm = spans("bench.window")
            span_cm.__enter__()
        batch = nxt
        with spans("bench.step"):
            state, met = step(state, batch)
        i += 1
        nxt = batch_of(traffic, vocab, seed, i)
        with spans("bench.wait"):
            loss = float(met["loss"])
        counters["steps"] += 1
        if tracing and counters["steps"] == trace_steps[1]:
            span_cm.__exit__(None, None, None)
            jax.profiler.stop_trace()
            counters["trace_window_s"] = time.perf_counter() - tt0
            counters["traced_steps"] = trace_steps[1] - trace_steps[0]
            tracing = False
            trace_dir = None
        if time.perf_counter() - t0 >= seconds and not tracing:
            break
    elapsed = time.perf_counter() - t0
    counters.update(elapsed_s=elapsed, tokens=counters["steps"] * tokens,
                    last_loss=loss)
    return state, counters


# ---------------------------------------------------------------- reference

def reference_steps(config: dict, traffic: dict, seed: int, n_check: int,
                    precision: str = "fp32"):
    """The checked steps in fp32 (or the ``precision`` control): losses,
    per-leaf norms of the first clipped gradient and of each trainable
    leaf's change after ``n_check`` AdamW steps."""
    tr = config["train"]
    key = W.root_key(seed)
    q = config["adapters"]["quanta"]
    tnames = list(q["targets"])
    ts, ss = {}, {}
    init = jax.jit(lambda k: W.quanta_stacked(
        config, k, q, jnp.arange(1) + q["train_tenant"], trained=False))(key)
    for tgt in tnames:
        ts[tgt] = tuple(a[0] for a in init[tgt][0])
        ss[tgt] = tuple(a[0] for a in init[tgt][1])

    def layer_adapters(layer, sl):
        t_l, s_l = sl
        return {tgt: ("quanta", t_l[tgt], s_l[tgt],
                      q["targets"][tgt]["dims_in"], q["pairs"])
                for tgt in tnames}

    def seq_loss(t, s, k, tokens, labels):
        hid = R.hidden(config, k, tokens, layer_adapters, (t, s),
                       nf4=False, precision=precision)
        return R.mean_nll(config, k, hid, labels, precision)

    grad_fn = jax.jit(jax.value_and_grad(seq_loss))

    lr, b1, b2, eps = tr["lr"], tr["b1"], tr["b2"], tr["eps"]
    m = jax.tree_util.tree_map(jnp.zeros_like, ts)
    v = jax.tree_util.tree_map(jnp.zeros_like, ts)
    t_cur = ts
    def clipped(g):
        gn = math.sqrt(sum(float(jnp.sum(x * x))
                           for x in jax.tree_util.tree_leaves(g)))
        clip = min(1.0, tr["max_grad_norm"] / max(gn, 1e-12))
        return jax.tree_util.tree_map(lambda x: x * clip, g)

    def norms(tree):
        return {tgt: [float(jnp.linalg.norm(x)) for x in tree[tgt]]
                for tgt in tnames}

    losses, g_first, half = [], None, None
    for i in range(n_check):
        rows = generate.train_rows(traffic, config["vocab_size"], seed, i)
        loss_sum, g_sum = 0.0, None
        for j, r in enumerate(rows):
            lv, g = grad_fn(t_cur, ss, key, jnp.asarray(r[:-1]),
                            jnp.asarray(r[1:]))
            loss_sum += float(lv)
            g_sum = g if g_sum is None else jax.tree_util.tree_map(
                jnp.add, g_sum, g)
            if i == 0 and j + 1 == len(rows) // 2:
                # the fault that drops half of the batch and takes the
                # mean over the rest
                half = {"loss": loss_sum / (j + 1), "grad": norms(clipped(
                    jax.tree_util.tree_map(lambda x: x / (j + 1), g_sum)))}
        g = clipped(jax.tree_util.tree_map(lambda x: x / len(rows), g_sum))
        losses.append(loss_sum / len(rows))
        if i == 0:
            g_first = g
        k = i + 1
        m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b,
                                   v, g)
        t_cur = jax.tree_util.tree_map(
            lambda p, a, b: p - lr * (a / (1 - b1 ** k))
            / (jnp.sqrt(b / (1 - b2 ** k)) + eps), t_cur, m, v)
    change = jax.tree_util.tree_map(lambda a, b: a - b, t_cur, ts)
    return {"loss": losses, "grad": norms(g_first), "change": norms(change),
            "half": half}


def program_readings(prog: dict, q: dict) -> dict:
    """The program's per-leaf norms in the reference's layout
    (``{target: [norm per tensor]}``); the frozen copy's leaves, whose
    reference gradient is zero, are returned apart."""
    out = {"loss": prog["loss"], "grad": {}, "change": {},
           "frozen_change": []}
    for name, g, c in zip(prog["names"], prog["grad"], prog["change"]):
        tgt = next(t for t in q["targets"] if f"'{t}'" in name)
        idx = int(name.rsplit("[", 1)[1].rstrip("]"))
        if ".frozen" in name:
            out["frozen_change"].append(float(c))
            continue
        out["grad"].setdefault(tgt, {})[idx] = float(g)
        out["change"].setdefault(tgt, {})[idx] = float(c)
    return out


def compare(got: dict, ref: dict, config: dict) -> dict:
    """Numbers the check compares, of ``got`` (the program's readings,
    or the control's or a fault's) against the fp32 reference: each
    step's loss gap relative to the reference, and the worst leaf's gap
    of gradient and change norms relative to the larger of that leaf's
    reference norm and the median leaf's.  Leaves whose reference
    gradient is under a thousandth of the median leaf's are left out."""
    q = config["adapters"]["quanta"]
    keys = [(t, i) for t in q["targets"] for i in range(len(q["pairs"]))]
    rg = np.array([ref["grad"][t][i] for t, i in keys])
    med_g = float(np.median(rg))
    live = rg >= 1e-3 * med_g

    def worst(kind):
        if kind not in got:
            return None
        refv = np.array([ref[kind][t][i] for t, i in keys])
        med = float(np.median(refv))
        gaps = [abs(got[kind][t][i] - r) / max(r, med)
                for (t, i), r, ok in zip(keys, refv, live) if ok]
        return float(max(gaps)) if gaps else float("inf")

    out = {"loss_gap": float(max(abs(a - b) / abs(b) for a, b in
                                 zip(got["loss"], ref["loss"]))),
           "grad_gap": worst("grad"), "change_gap": worst("change")}
    if "frozen_change" in got:
        out["frozen_change"] = float(max(got["frozen_change"] + [0.0]))
    return out


# --------------------------------------------------------------------- cell

def run(cell, args, clock, devices, spans, peaks, trace_dir):
    from bench.harness import Context, device_info, log, read_per_layer

    config, traffic = cell.config, cell.traffic
    chk = config["check"]
    n_check = chk["steps"]
    log(f"{clock():.1f}s building")
    state, step = build(config, args.seed)
    state, prog = first_steps(state, step, traffic, config, args.seed,
                              n_check, spans)
    jax.block_until_ready(state.peft)
    setup_s = clock()
    log(f"{setup_s:.1f}s first steps {prog['loss']}")

    ts = tuple(traffic["trace_steps"]) if trace_dir is not None else (0, 0)
    state, counters = run_window(state, step, traffic, config, args.seed,
                                 n_check, args.seconds, spans, trace_dir, ts)
    info = device_info(devices)
    finite = bool(np.isfinite(counters["last_loss"]))
    del state, step
    gc.collect()

    log(f"{clock():.1f}s window closed: {counters}")
    ref = reference_steps(config, traffic, args.seed, n_check)
    log(f"{clock():.1f}s reference done {ref['loss']}")
    nums = compare(program_readings(prog, config["adapters"]["quanta"]),
                   ref, config)
    compared = {
        "loss_gap": {"value": nums["loss_gap"], "limit": chk["loss_gap"]},
        "grad_gap": {"value": nums["grad_gap"], "limit": chk["grad_gap"]},
        "change_gap": {"value": nums["change_gap"],
                       "limit": chk["change_gap"]},
        "frozen_change": {"value": nums["frozen_change"], "limit": 0.0},
    }
    for c in compared.values():
        c["ok"] = bool(c["value"] <= c["limit"])

    metrics = {}
    rate = counters["tokens"] / counters["elapsed_s"]
    counters["tokens_per_s"] = rate
    if args.trace:
        from bench import trace as T

        ctx = Context(config=config, traffic=traffic, peaks=peaks,
                      counters=counters, window_s=counters["elapsed_s"],
                      trace=T.reduce(trace_dir) if trace_dir else None)
        metrics = read_per_layer(cell, ctx)
        if ctx.trace is not None:
            info["busy_s"] = ctx.trace.busy_s
            info["window_s"] = ctx.trace.window_s
    else:
        values = {"train_tokens_per_s": rate, "setup_s": setup_s}
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result = {
        "correct": all(c["ok"] for c in compared.values()) and finite,
        "attempted": counters["steps"] + n_check,
        "failed": 0 if finite else 1,
        "metrics": metrics, "device": info,
    }
    if args.trace and ctx.trace is not None:
        result["breakdown"] = ctx.trace.breakdown()
    return result, compared
