"""Streaming SLA-scheduled serving through the async front end.

    PYTHONPATH=src python examples/serve_streaming.py

Builds a smoke transformer, wraps the continuous-batching engine in
``ServeFrontend``, and serves an open-loop Poisson arrival schedule of
two latency classes (``interactive``: 250ms TTFT target, ``batch``:
2.5s) with per-token streaming:

* the front end runs in a worker thread (``fe.drain()``), dispatching
  double-buffered decode ticks — tick N+1 is dispatched from the
  device-resident sampled tokens before tick N's tokens are even
  fetched (``fe.stats["chained"]`` counts how often that overlap
  engaged),
* each ``submit()`` returns a ``TokenStream``; the main thread consumes
  them as tokens land and prints per-request TTFT and per-token gaps,
* admission is earliest-deadline-first across the class queues, so an
  interactive request arriving after a batch request can still admit
  first — while outputs stay token-for-token identical to the plain
  closed-loop engine (asserted below; scheduling never changes greedy
  results, only latency),
* the engine's gauges (``ttft_p50/p99``, tick-latency percentiles, peak
  per-class queue depth) summarize the run at the end.

``--asyncio`` serves the same schedule on an asyncio event loop instead
(``await fe.serve()`` + ``async for tok in stream``).
"""

import argparse
import asyncio
import threading
import time

import jax
import numpy as np

from repro.configs import get_smoke
from repro.models import build_model
from repro.serve import (
    Request, ServeFrontend, ServingEngine, poisson_arrivals,
)

PROMPTS = [[3, 141, 59], [26, 5], [35, 89, 79, 32], [38, 46],
           [2, 7, 18], [91, 14, 5, 5], [60, 61], [7] * 9]
MAX_NEW = 8


def _requests(now: float):
    arrivals = poisson_arrivals(np.random.default_rng(0), 40.0,
                                len(PROMPTS), start=now + 0.05)
    return [
        Request(uid=i, prompt=list(p), max_new_tokens=MAX_NEW,
                arrival_time=float(arrivals[i]),
                latency_class="interactive" if i % 2 == 0 else "batch")
        for i, p in enumerate(PROMPTS)
    ]


def main(use_asyncio: bool = False):
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # closed-loop reference: scheduling must never change greedy outputs
    ref_engine = ServingEngine(model, params, n_slots=4, max_len=64,
                               cache="paged", block_size=16)
    ref_reqs = [Request(uid=i, prompt=list(p), max_new_tokens=MAX_NEW)
                for i, p in enumerate(PROMPTS)]
    for r in ref_reqs:
        ref_engine.submit(r)
    ref_engine.run()
    ref = {r.uid: r.output for r in ref_reqs}

    engine = ServingEngine(model, params, n_slots=4, max_len=64,
                           cache="paged", block_size=16)
    fe = ServeFrontend(engine)
    reqs = _requests(engine.clock())
    streams = [fe.submit(r) for r in reqs]

    if use_asyncio:
        async def consume(stream):
            req = stream.request
            async for _ in stream:
                pass
            print(f"req {req.uid} [{req.latency_class:11s}] done: "
                  f"{stream.tokens}")

        async def run():
            server = asyncio.create_task(fe.serve())
            await asyncio.gather(*(consume(s) for s in streams))
            await server

        asyncio.run(run())
    else:
        worker = threading.Thread(target=fe.drain)
        worker.start()
        for s in streams:
            req = s.request
            first = None
            for _ in s:                      # tokens land incrementally
                if first is None:
                    first = s.token_times[0] - req.arrival_time
            gaps = np.diff(s.token_times) * 1e3
            print(f"req {req.uid} [{req.latency_class:11s}] "
                  f"ttft={first * 1e3:6.1f}ms "
                  f"gap_p50={np.percentile(gaps, 50):5.2f}ms "
                  f"tokens={s.tokens}")
        worker.join()

    assert {r.uid: r.output for r in reqs} == ref, \
        "front-end scheduling changed greedy outputs"
    print("all streamed outputs match the closed-loop engine")
    s = engine.stats
    print(f"frontend: {fe.stats['chained']} chained (double-buffered) / "
          f"{fe.stats['host_dispatch']} host dispatches over "
          f"{fe.stats['ticks']} ticks")
    print(f"gauges: ttft_p50={s['ttft_p50'] * 1e3:.1f}ms "
          f"ttft_p99={s['ttft_p99'] * 1e3:.1f}ms "
          f"tick_p50={s['tick_p50'] * 1e6:.0f}us "
          f"qdepth_peak={s.get('queue_depth_peak', {})}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--asyncio", action="store_true",
                    help="drive the front end on an asyncio event loop "
                         "instead of a worker thread")
    t0 = time.perf_counter()
    main(use_asyncio=ap.parse_args().asyncio)
    print(f"({time.perf_counter() - t0:.1f}s total)")
