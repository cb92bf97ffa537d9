"""Serving cells: ``ServeFrontend`` (``submit`` / ``tick``) over a
``ServingEngine`` with an nf4 base, a paged KV pool and, where the mix
names tenants, an ``AdapterPool`` smaller than its store.

Set-up makes the weights and tenants on the device from the seed, warms
every program shape the mix can reach through the public entry points,
and pre-submits the window's requests with their due times.  The window
ticks the front end for ``--seconds``; afterwards it ticks on, without
new arrivals, until every request due in the window has its first token
(at most a minute), so a late first token counts as late.  Then the
program's state is freed and the plain reference scores a seeded sample
of the finished requests.
"""

from __future__ import annotations

import gc
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate, weights as W
from bench.reference import dense as R

GRACE_S = 60.0
FILL_S = 120.0


# ------------------------------------------------------------------ program

def model_config(config: dict, serve: dict):
    """The program's ``ModelConfig`` for this file, checked against the
    widths the file states."""
    from repro.configs import get_config

    cfg = get_config(config["arch"]).replace(
        n_layers=config["num_hidden_layers"], attn_backend="pallas",
        peft_backend="pallas", kv_quant=serve.get("kv_quant"),
        quant_block_size=serve["quant_block"])
    check_widths(cfg, config)
    return cfg


def check_widths(cfg, config: dict) -> None:
    pairs = [("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
             ("n_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
             ("d_ff", "intermediate_size"), ("vocab_size", "vocab_size"),
             ("tie_embeddings", "tie_word_embeddings"),
             ("rope_theta", "rope_theta"), ("norm_eps", "rms_norm_eps")]
    bad = [(a, getattr(cfg, a), config[b]) for a, b in pairs
           if getattr(cfg, a) != config[b]]
    if bad:
        raise SystemExit(f"bench: the program's config differs from the "
                         f"file: {bad}")
    # a published setting the file lists as not applied must stay absent
    # from the program too: the reference does not apply it either
    stale = [k for k in config.get("departures", {})
             if k not in config and hasattr(cfg, k)]
    if stale:
        raise SystemExit(f"bench: the program now has {stale}, which the "
                         f"file lists as departures")


def nf4_params(config: dict, key, block: int):
    """The served base in one jitted call: each layer's projections are
    made in bf16 and packed to nf4 as they are made."""
    from repro.core.quantize import quantize_linear

    def proj_fn(w):
        return quantize_linear(w, "nf4", block_size=block)

    # the key is an argument, not a constant of the program, so every
    # seed finds the same compiled program in the cache
    return jax.jit(lambda k: W.base_params(config, k, proj_fn))(key)


def adapter_template(config: dict, peft, targets):
    """The program's ``AdapterSet`` structure for one tenant of ``peft``
    on ``targets`` (shapes only, no allocation)."""
    from repro.core.peft import attach

    shapes = W.proj_shapes(config)
    n_layers = config["num_hidden_layers"]
    struct = {"layers": {"attn": {
        t: jax.ShapeDtypeStruct((n_layers,) + shapes[t], jnp.bfloat16)
        for t in targets}}}
    return jax.eval_shape(
        lambda p: attach(jax.random.PRNGKey(0), p, peft)[1], struct)


def fill_template(template, arrays: dict, i: int):
    """Tenant ``i``'s ``AdapterSet``: the template's leaves replaced by
    host copies of the generated factors (registry tenants live on the
    host; a load copies them to the resident bank)."""
    def leaf(path, _):
        names = [getattr(p, "key", getattr(p, "name", getattr(p, "idx", None)))
                 for p in path]
        target = next(n for n in names if n in arrays)
        attr = names[names.index(target) + 1]
        idx = names[-1] if isinstance(names[-1], int) else None
        src = arrays[target]
        if attr in ("tensors", "frozen"):
            return src[0 if attr == "tensors" else 1][idx][i]
        return src[0 if attr == "a" else 1][i]

    return jax.tree_util.tree_map_with_path(leaf, template)


def tenant_specs(config: dict):
    """``[(name, kind, local index)]`` for every tenant of the config."""
    out = []
    for kind in ("quanta", "lora"):
        spec = config["adapters"].get(kind)
        for j in range(spec["tenants"] if spec else 0):
            out.append((f"{kind}{j}", kind, j))
    return out


def build_store(config: dict, key):
    """Every tenant of the config, made by one jitted, vmapped generator
    per adapter structure and registered host-side."""
    from repro.core.peft import PeftConfig
    from repro.serve.adapter_pool import AdapterStore

    specs = tenant_specs(config)
    store = AdapterStore(max_tenants=max(len(specs), 1))
    ad = config["adapters"]
    made = {}
    if "quanta" in ad:
        q = ad["quanta"]
        peft = PeftConfig(method="quanta", n_axes=len(q["pairs"]),
                          scheme=q["scheme"], fold=False,
                          targets=tuple(rf".*/{t}$" for t in q["targets"]))
        tmpl = adapter_template(config, peft, q["targets"])
        arrays = jax.device_get(jax.jit(lambda k: W.quanta_stacked(
            config, k, q, jnp.arange(q["tenants"]), trained=True))(key))
        made["quanta"] = (tmpl, arrays)
    if "lora" in ad:
        lo = ad["lora"]
        peft = PeftConfig(method="lora", rank=lo["rank"], alpha=lo["alpha"],
                          targets=tuple(rf".*/{t}$" for t in lo["targets"]))
        tmpl = adapter_template(config, peft, lo["targets"])
        arrays = jax.device_get(jax.jit(lambda k: W.lora_stacked(
            config, k, lo, jnp.arange(lo["tenants"])))(key))
        made["lora"] = (tmpl, arrays)
    for name, kind, j in specs:
        tmpl, arrays = made[kind]
        store.register(name, fill_template(tmpl, arrays, j))
    return store, specs


def build_engine(config: dict, seed: int):
    from repro.models import build_model
    from repro.serve import ServeFrontend, ServingEngine
    from repro.serve.adapter_pool import AdapterPool

    from bench.harness import log

    serve = config["serve"]
    cfg = model_config(config, serve)
    key = W.root_key(seed)
    params = nf4_params(config, key, serve["quant_block"])
    log("nf4 base made")
    pool, specs = None, []
    if config.get("adapters"):
        store, specs = build_store(config, key)
        log(f"{len(specs)} tenants registered")
        pool = AdapterPool.build(params, store,
                                 capacity=serve["pool_capacity"])
    engine = ServingEngine(
        build_model(cfg), params, adapters=pool, n_slots=serve["n_slots"],
        max_len=serve["max_len"], seq_bucket=serve["seq_bucket"],
        cache="paged", block_size=serve["block_size"],
        n_blocks=serve["n_blocks"], base_quant="nf4",
        kv_quant=serve.get("kv_quant"),
        prefill_chunk=serve.get("prefill_chunk"))
    return ServeFrontend(engine), specs


# ------------------------------------------------------------------ warm-up

def warm_lengths(serve: dict, shortest: int, longest: int):
    """One prompt length per program shape admission can reach from
    prompts of ``shortest`` to ``longest`` tokens (a preempted request
    re-admits with its output as prompt): each wave bucket up to the
    chunk size, then each chunked staging extent."""
    chunk = serve.get("prefill_chunk") or serve["max_len"]
    bucket = serve["seq_bucket"]
    first = -(-shortest // bucket) * bucket
    waves = list(range(first, min(chunk, longest) + 1, bucket))
    staged = []
    if serve.get("prefill_chunk") and longest > chunk:
        lo = max(-(-shortest // chunk), 2) * chunk
        staged = [min(x, serve["max_len"] - 2)
                  for x in range(lo, longest + chunk, chunk)]
    return waves, staged


def warm_up(front, config: dict, traffic: dict, specs, spans) -> None:
    """Drive every program shape through ``submit``/``tick``: a wave of
    each size at each bucket, each chunked staging extent, and a load of
    each tenant structure."""
    from repro.serve import Request

    serve = config["serve"]
    eng = front.engine
    longest = min(traffic["prompt"]["max"] + traffic["output"]["max"],
                  serve["max_len"] - 2)
    waves, staged = warm_lengths(serve, traffic["prompt"]["min"], longest)
    uid = [10 ** 9]

    def run(batch):
        for plen, tenant in batch:
            uid[0] += 1
            front.submit(Request(uid=uid[0], prompt=[1] * plen,
                                 max_new_tokens=2), adapter=tenant)
        with spans("bench.warm"):
            front.drain()

    for b in waves:
        for n in range(1, eng.n_slots + 1):
            run([(b, None)] * n)
    for s in staged:
        run([(s, None)])
    seen = set()
    for name, kind, _ in specs:
        if kind not in seen:
            seen.add(kind)
            run([((waves or staged)[0], name)])


# ------------------------------------------------------------------- window

def offline(requests) -> bool:
    """Every request due at the start: an offline batch."""
    return all(r.arrival == 0.0 for r in requests)


def run_window(front, requests, specs, seconds: float, spans, trace_dir=None,
               trace_span=(0.0, 0.0), finished_tokens: int = 0):
    """Offer the requests on their schedule for ``seconds``; returns the
    per-request records and the window's counters.  An offline batch is
    queued whole, and its window opens once every slot is full."""
    from repro.serve import Request

    eng = front.engine
    clock = eng.clock
    names = [s[0] for s in specs]
    pool = eng.pool
    t0 = clock() + 0.05
    streams = []
    for r in requests:
        if r.arrival > seconds:
            break
        req = Request(uid=r.uid, prompt=r.prompt.tolist(),
                      max_new_tokens=r.max_new, arrival_time=t0 + r.arrival,
                      latency_class=r.latency_class)
        streams.append((r, front.submit(
            req, adapter=None if r.tenant is None else names[r.tenant])))
    batch = offline(requests)
    if batch:
        # the window opens once the engine holds all it can: every slot
        # busy, or a tick that began with no prompt in chunks admitted
        # nothing (the KV pool has no room for the queue's head)
        stop = clock() + FILL_S
        with spans("bench.fill"):
            while clock() < stop:
                before = sum(s is not None for s in eng.slots)
                idle = eng._chunking is None
                front.tick()
                busy = sum(s is not None for s in eng.slots)
                if eng._chunking is None and (
                        busy == eng.n_slots or (idle and busy <= before)):
                    break
        t0 = clock()
    loads0 = pool.loads if pool is not None else 0
    preempt0 = eng.stats.get("preemptions", 0)
    end = t0 + seconds
    counters = {"ticks": 0, "tick_s": 0.0, "block_share_sum": 0.0,
                "decode_steps": 0, "live_tokens": 0, "live_slots": 0}
    tracing = False
    blocks_total = max(eng.stats.get("blocks_total", 0), 1)
    # per tick: seconds, whether a prefill was in chunks, loads,
    # preemptions, and the CPU seconds of this thread
    tick_log = []
    gc_log = []            # (generation, seconds) of each collection
    gc_start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            gc_log.append((info["generation"],
                           time.perf_counter() - gc_start[0]))

    gc.callbacks.append(on_gc)
    while True:
        now = clock()
        if trace_dir is not None and not tracing and \
                now >= t0 + trace_span[0] and now < t0 + trace_span[1]:
            jax.profiler.start_trace(trace_dir)
            tracing, trace_t0 = True, clock()
            span_cm = spans("bench.window")
            span_cm.__enter__()
        if tracing and now >= t0 + trace_span[1]:
            span_cm.__exit__(None, None, None)
            jax.profiler.stop_trace()
            counters["trace_window_s"] = clock() - trace_t0
            tracing = False
            trace_dir = None
        if now >= end:
            break
        active = [s for s in eng.slots if s is not None]
        chunking = eng._chunking is not None
        loads_a = pool.loads if pool is not None else 0
        preempt_a = eng.stats.get("preemptions", 0)
        t_a, cpu_a = time.perf_counter(), time.thread_time()
        with spans("bench.tick"):
            did = front.tick()
        dt = time.perf_counter() - t_a
        if did:
            tick_log.append((dt, chunking or eng._chunking is not None,
                             (pool.loads if pool is not None else 0) - loads_a,
                             eng.stats.get("preemptions", 0) - preempt_a,
                             time.thread_time() - cpu_a))
            counters["ticks"] += 1
            counters["tick_s"] += dt
            counters["block_share_sum"] += \
                eng.stats.get("blocks_in_use", 0) / blocks_total
            if active:
                counters["decode_steps"] += 1
                counters["live_slots"] += len(active)
                counters["live_tokens"] += sum(
                    len(q.prompt) + len(q.output) for q in active)
                if tracing:
                    tenants = {q.adapter for q in active if q.adapter}
                    for k, v in (
                            ("traced_decode_steps", 1),
                            ("traced_live_slots", len(active)),
                            ("traced_live_tokens", sum(
                                len(q.prompt) + len(q.output)
                                for q in active)),
                            ("traced_quanta_tenants", sum(
                                t.startswith("quanta") for t in tenants)),
                            ("traced_lora_tenants", sum(
                                t.startswith("lora") for t in tenants))):
                        counters[k] = counters.get(k, 0) + v
        else:
            with spans("bench.idle"):
                time.sleep(0.0005)
    if tracing:
        span_cm.__exit__(None, None, None)
        jax.profiler.stop_trace()
        counters["trace_window_s"] = clock() - trace_t0
    counters["adapter_loads"] = (pool.loads if pool is not None else 0) - loads0
    counters["preemptions"] = eng.stats.get("preemptions", 0) - preempt0
    counters["window_s"] = seconds
    counters["t0"] = t0
    counters["tick_log"] = tick_log
    gc.callbacks.remove(on_gc)
    counters["gc_log"] = gc_log
    # late first tokens are late, not missing: tick on (no new arrivals)
    # until every due request has one and the finished requests hold
    # ``finished_tokens`` served tokens (or all are done); an offline
    # batch's queue is longer than the window by design, so there only
    # the finished tokens are waited for
    deadline = end + GRACE_S

    def waiting():
        if not batch and any(not s.token_times for _, s in streams):
            return True
        done = sum(len(s.tokens) for _, s in streams if s.done)
        return done < finished_tokens and not all(s.done for _, s in streams)

    while clock() < deadline and waiting():
        if not front.tick():
            time.sleep(0.0005)
    return t0, end, streams, counters


def latency_metrics(t0, end, streams, counters) -> Dict[str, float]:
    """TTFT and inter-token gaps from the streams' own token stamps;
    tokens landed in the window over its length."""
    ttft, gaps, landed = [], [], 0
    batch = offline([r for r, _ in streams])
    for r, s in streams:
        times = s.token_times
        if batch and not (times and times[0] <= end):
            continue         # never started: beyond the offline window
        ttft.append(times[0] - (t0 + r.arrival) if times else float("inf"))
        inwin = [t for t in times if t0 <= t <= end]
        landed += len(inwin)
        gaps.extend(np.diff(inwin).tolist())
    return {"ttft": ttft, "gaps": gaps, "landed": landed,
            "attempted": len(ttft),
            "missing": sum(1 for x in ttft if not np.isfinite(x))}


def log_spread(lat, tick_log) -> None:
    """Where the tail of the gaps comes from: the gaps' quantiles, and the
    ticks' times split by what each tick carried."""
    from bench.harness import log

    gaps = np.asarray(lat["gaps"], np.float64)
    if not len(gaps):
        return
    q = np.percentile(gaps, [50, 90, 95, 99], method="higher")
    log(f"gaps n={len(gaps)} p50={1e3 * q[0]:.1f} p90={1e3 * q[1]:.1f} "
        f"p95={1e3 * q[2]:.1f} p99={1e3 * q[3]:.1f} "
        f"max={1e3 * gaps.max():.1f} over_1s={int((gaps > 1).sum())} ms")
    if not tick_log:
        return
    dt = np.array([t[0] for t in tick_log])
    kinds = {"plain": [not c and not lo and not p
                       for _, c, lo, p, _ in tick_log],
             "chunk": [t[1] for t in tick_log],
             "load": [t[2] > 0 for t in tick_log],
             "preempt": [t[3] > 0 for t in tick_log]}
    parts = []
    for name, mask in kinds.items():
        sel = dt[np.asarray(mask, bool)]
        if len(sel):
            parts.append(f"{name} n={len(sel)} p50={1e3 * np.median(sel):.1f} "
                         f"max={1e3 * sel.max():.1f}")
    log("ticks " + "; ".join(parts))
    log("tick_ms " + " ".join(
        f"{1e3 * d:.0f}{'c' if c else ''}{'l' if lo else ''}{'p' if p else ''}"
        for d, c, lo, p, _ in tick_log))
    log("ticks over 0.5 s (index, wall s, this thread's CPU s): " + " ".join(
        f"({i}, {t[0]:.3f}, {t[4]:.3f})" for i, t in enumerate(tick_log)
        if t[0] > 0.5))


def p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95,
                               method="higher"))


# ---------------------------------------------------------------- reference

def sample_finished(streams, seed: int, min_tokens: int, max_requests: int):
    """The longest finished request and a seeded draw of others, until
    ``min_tokens`` served tokens or ``max_requests``."""
    done = [(r, s) for r, s in streams if s.done]
    if not done:
        return []
    done.sort(key=lambda x: -(len(x[0].prompt) + len(x[1].tokens)))
    picked = [done[0]]
    rest = done[1:]
    order = np.random.default_rng(np.random.SeedSequence([seed, 6])
                                  ).permutation(len(rest))
    for i in order:
        if sum(len(s.tokens) for _, s in picked) >= min_tokens or \
                len(picked) >= max_requests:
            break
        picked.append(rest[i])
    return [(r.prompt, np.asarray(s.tokens, np.int32), r.tenant)
            for r, s in picked]


def _ref_adapters(config, key, use, local):
    """``layer_adapters(layer, _)`` for one served request: each adapter
    kind of the config, weighted by ``use[kind]`` (1 for the request's
    own kind, 0 otherwise: an exact zero), for tenant ``local[kind]``.
    One program then serves every kind, base requests included."""
    ad = config["adapters"]
    shapes = W.proj_shapes(config)

    def fn(layer, _):
        out = {}
        if "quanta" in ad:
            q = ad["quanta"]
            for tgt, t in q["targets"].items():
                ts, ss = W.quanta_layer(key, q, tgt, local["quanta"], layer,
                                        trained=True)
                out.setdefault(tgt, []).append(
                    (use["quanta"], ("quanta", ts, ss, t["dims_in"],
                                     q["pairs"])))
        if "lora" in ad:
            lo = ad["lora"]
            for tgt in lo["targets"]:
                a, b = W.lora_layer(key, lo, tgt, shapes[tgt], local["lora"],
                                    layer)
                out.setdefault(tgt, []).append(
                    (use["lora"], ("lora", a, b, lo["alpha"] / lo["rank"])))
        return {tgt: ("mix", parts) for tgt, parts in out.items()}

    return fn


def reference_gaps(config: dict, seed: int, sample, specs,
                   precisions=("fp32",), altered: bool = False):
    """Per sampled request, per served token: how far the reference's
    logit of that token lies below its best.  With ``"fp8"`` also
    returns, per position, the gap of the token the fp8 control puts
    first; with ``altered``, per request, the gap of one served token
    replaced by a seeded other (the fault of a token altered where it
    is produced)."""
    key = W.root_key(seed)
    span = config["serve"]["max_len"]
    kinds = [k for k in ("quanta", "lora") if k in config.get("adapters", {})]
    # one program for every request and seed: the key and the tenant are
    # arguments, and the logits' rows are a multiple of 512 (the longest
    # output a mix asks for so far)
    rows = -(-max(len(t) for _, t, _ in sample) // 512) * 512

    def f(k, tokens, start, use, local):
        res = {}
        for prec in precisions:
            hid = R.hidden(config, k, tokens,
                           _ref_adapters(config, k, use, local),
                           None, nf4=True, precision=prec)
            res[prec] = R.logits_at(config, k, hid, start, rows, prec)
        return res

    program = jax.jit(f)
    gaps, ctrl, alt = [], [], []
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    for prompt, served, tenant in sample:
        kind, own = ("base", 0) if tenant is None else specs[tenant][1:]
        use = {k: jnp.float32(k == kind) for k in kinds}
        local = {k: jnp.int32(own if k == kind else 0) for k in kinds}
        seq = np.zeros((span,), np.int32)
        full = np.concatenate([prompt, served[:-1]])
        seq[:len(full)] = full
        res = program(key, jnp.asarray(seq), len(prompt) - 1, use, local)
        ref = np.asarray(res["fp32"])[:len(served)]
        best = ref.max(-1)
        gaps.append(best - ref[np.arange(len(served)), served])
        if "fp8" in res:
            lo = np.asarray(res["fp8"])[:len(served)]
            ctrl.append(best - ref[np.arange(len(served)), lo.argmax(-1)])
        if altered:
            pos = int(rng.integers(len(served)))
            tok = (int(served[pos]) + 1 + int(rng.integers(
                config["vocab_size"] - 1))) % config["vocab_size"]
            alt.append(float(best[pos] - ref[pos, tok]))
    if altered:
        return gaps, ctrl, alt
    return gaps, ctrl


# --------------------------------------------------------------------- cell

def run(cell, args, clock, devices, spans, peaks, trace_dir):
    from bench.harness import Context, device_info, log, read_per_layer

    config, traffic = cell.config, cell.traffic
    log(f"{clock():.1f}s building")
    front, specs = build_engine(config, args.seed)
    log(f"{clock():.1f}s engine built")
    requests = generate.serve_requests(traffic, config["vocab_size"],
                                       args.seed, len(specs), args.seconds)
    warm_up(front, config, traffic, specs, spans)
    jax.block_until_ready(front.engine.cache)
    setup_s = clock()
    log(f"{setup_s:.1f}s warm; the collector tracks "
        f"{len(gc.get_objects())} objects")

    span = (0.0, 0.0)
    if trace_dir is not None:
        span = tuple(traffic["trace_span"])
    t0, end, streams, counters = run_window(
        front, requests, specs, args.seconds, spans, trace_dir, span,
        finished_tokens=config["check"]["min_tokens"])
    lat = latency_metrics(t0, end, streams, counters)
    info = device_info(devices)
    log(f"{clock():.1f}s window closed: "
        f"{ {k: v for k, v in counters.items() if not k.endswith('_log')} }")
    log_spread(lat, counters.get("tick_log", []))
    gcs = counters.get("gc_log", [])
    for gen in range(3):
        ts = [t for g, t in gcs if g == gen]
        if ts:
            log(f"gc gen{gen}: {len(ts)} collections, "
                f"{1e3 * sum(ts):.1f} ms in all, longest {1e3 * max(ts):.1f} ms")
    del front
    gc.collect()

    chk = config["check"]
    sample = sample_finished(streams, args.seed, chk["min_tokens"],
                             chk["max_requests"])
    gaps, _ = reference_gaps(config, args.seed, sample, specs) \
        if sample else ([], [])
    # two numbers: the mean over served tokens catches a lower precision
    # (the fp8 control), the widest gap one token altered where it is
    # produced (which moves the mean of some hundreds of tokens too little)
    widest = float(max(g.max() for g in gaps)) if gaps else float("inf")
    mean = float(np.concatenate(gaps).mean()) if gaps else float("inf")
    n_tok = sum(len(g) for g in gaps)
    log(f"{clock():.1f}s reference done over {len(sample)} requests, "
        f"{n_tok} served tokens")
    for (prompt, served, tenant), g in zip(sample, gaps):
        kind = "base" if tenant is None else specs[tenant][1]
        log(f"  {kind} prompt={len(prompt)} served={len(served)} "
            f"widest={g.max():.4f} at {int(g.argmax())} "
            f"mean={g.mean():.4f}")
    compared = {
        "mean_logit_gap": {"value": mean, "limit": chk["mean_logit_gap"]},
        "max_logit_gap": {"value": widest, "limit": chk["max_logit_gap"]},
    }
    for c in compared.values():
        c["ok"] = bool(c["value"] <= c["limit"])

    metrics = {}
    if args.trace:
        from bench import trace as T

        counters["gaps"] = lat["gaps"]
        ctx = Context(config=config, traffic=traffic, peaks=peaks,
                      counters=counters, window_s=args.seconds,
                      trace=T.reduce(trace_dir) if trace_dir else None)
        metrics = read_per_layer(cell, ctx)
        log(f"{clock():.1f}s trace read")
        if ctx.trace is not None:
            info["busy_s"] = ctx.trace.busy_s
            info["window_s"] = ctx.trace.window_s
    else:
        values = {
            "ttft_p95_ms": 1e3 * p95(lat["ttft"]) if lat["ttft"] else None,
            "itl_p95_ms": 1e3 * p95(lat["gaps"]) if lat["gaps"] else None,
            "serve_tokens_per_s": lat["landed"] / args.seconds,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result = {
        "correct": all(c["ok"] for c in compared.values()) and
        lat["missing"] == 0,
        "attempted": lat["attempted"], "failed": lat["missing"],
        "metrics": metrics, "device": info,
    }
    if args.trace and ctx.trace is not None:
        result["breakdown"] = ctx.trace.breakdown()
    return result, compared
