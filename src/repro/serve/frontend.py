"""Async continuous-batching serving front end over ``ServingEngine``.

``ServeFrontend`` turns the engine's synchronous closed tick loop into a
host loop that OVERLAPS host work with device steps and streams tokens
as they land:

* **SLA-aware continuous batching** — ``submit()`` routes requests into
  latency-class queues (``repro.serve.scheduler.SLAScheduler``,
  default ``interactive``/``batch``) and admission each tick pulls from
  the earliest-deadline-first ready view instead of the engine FIFO.
  Preemption requeues into the class queues (``engine.requeue_hook``)
  and victim selection is SLA-aware (``engine.victim_hook``: evict the
  lowest-priority class, then the latest arrival) while still flowing
  through the engine's paged-arena machinery.
* **Double-buffered dispatch** — the fused decode returns device
  futures (JAX async dispatch); the front end samples them with the
  engine's device-side ``_sample`` jit and — when every in-flight slot
  provably SURVIVES the un-landed tick — chains the sampled-token
  array straight into the next decode dispatch, fetching the older
  tick's tokens only afterwards.  Host work (admission, block
  allocation, streaming) and the device step run concurrently.
  Slots freshly admitted between the two dispatches take their host
  token through the ``merge_tokens_fn`` jit (``where(fresh, host,
  chain)``).
* **Survival rule** (chain safety): a chained dispatch is only issued
  when no in-flight slot can complete in the un-landed tick — no
  ``eos_id``, token budget and ``max_len`` headroom >= 2, and (paged)
  block capacity for BOTH pending tokens ensurable without preemption.
  Anything else lands first (the engine's synchronous path), so
  streamed outputs are **token-for-token identical** to the closed
  loop by construction (pinned by ``tests/test_frontend.py`` across
  all three families, dense and paged, mixed adapter tenants).
  Hot-swap adapter pools (``serve.adapter_pool.AdapterPool``) compose
  transparently: pinning/unpinning rides the engine's admission and
  ``requeue_hook`` paths the front end already flows through, and a
  deferred tenant (all rows pinned) simply stays queued in its class.
* **Streaming** — ``submit()`` returns a :class:`TokenStream`: iterate
  it (``for tok in stream`` or ``async for tok in stream``) to receive
  tokens as their tick lands; ``result()`` blocks until EOS/budget and
  returns the full output.  Token timestamps back the open-loop
  harness's exact TTFT / per-token-latency percentiles
  (``benchmarks/serve_bench.py --open-loop``).
* **Prefill/decode interleave** — the engine's fixed one-chunk-per-tick
  chunked-prefill cadence is replaced by
  ``scheduler.InterleavePolicy``: chunk bursts sized by whether decode
  slots are active and by the admitting request's SLA priority.

Every jitted entry point the front end adds (``merge_tokens_fn``,
registered as ``merge_toks``; the engine's ``sample`` is registered by
the engine itself) carries a documented compile bound on
``engine.compile_guard``, so ``REPRO_SANITIZE=1`` holds the async loop
to the same retrace/leak discipline as the closed loop — ``tick()``
asserts the bounds under sanitize exactly like ``ServingEngine.step``.
"""

from __future__ import annotations

import asyncio
import functools
import queue as _queue
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize
from repro.serve.engine import Request, ServingEngine
from repro.serve.scheduler import (
    DEFAULT_CLASSES, InterleavePolicy, SLAClass, SLAScheduler, VirtualClock,
)

__all__ = ["ServeFrontend", "TokenStream"]

_SENTINEL = object()


class TokenStream:
    """Per-request streaming handle returned by :meth:`ServeFrontend.submit`.

    Tokens arrive as their tick LANDS (one dispatch late under double
    buffering, but in generation order and before the next tick's
    tokens).  One consumer per stream:

    * ``for tok in stream`` — blocking iteration (front end driven by
      another thread, or already drained),
    * ``async for tok in stream`` — the blocking get runs in the
      default executor so the event loop (e.g. ``frontend.serve()``)
      stays live,
    * ``stream.result()`` — drain to completion, return the full list.

    ``tokens`` / ``token_times`` accumulate every landed token and its
    engine-clock timestamp (the open-loop harness computes exact
    TTFT / per-token-latency percentiles from them).
    """

    def __init__(self, req: Request, clock):
        self.request = req
        self._clock = clock
        self._q: _queue.Queue = _queue.Queue()
        self.tokens: List[int] = []
        self.token_times: List[float] = []
        self.closed = False

    @property
    def done(self) -> bool:
        return self.request.done

    # producer side (the front end) -----------------------------------
    def _push(self, tok: int) -> None:
        self.tokens.append(tok)
        self.token_times.append(self._clock())
        self._q.put(tok)

    def _close(self) -> None:
        self.closed = True
        self._q.put(_SENTINEL)

    # consumer side ---------------------------------------------------
    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _SENTINEL:
                return
            yield item

    def __aiter__(self):
        return self

    async def __anext__(self) -> int:
        item = await asyncio.get_running_loop().run_in_executor(
            None, self._q.get
        )
        if item is _SENTINEL:
            raise StopAsyncIteration
        return item

    def result(self) -> List[int]:
        """Block until the stream closes; returns the full token list."""
        for _ in self:
            pass
        return self.tokens


class ServeFrontend:
    """SLA-scheduled, double-buffered, streaming front end.

    Drives a prefill-admission :class:`ServingEngine` through its
    front-end seams (``validate`` / ``_admit(queue=...)`` /
    ``dispatch_decode`` / ``_postprocess`` / the requeue+victim hooks).
    The engine's own FIFO stays empty; all queueing lives in the
    :class:`SLAScheduler`.

    ``stats``: ``ticks`` (front-end scheduling ticks), ``chained``
    (double-buffered dispatches that skipped the host round-trip),
    ``host_dispatch`` (synchronous fallbacks), plus the
    engine's own gauges (``engine.stats`` — ``queue_depth`` is written
    each tick from the scheduler's class queues).  Each tick is a
    ``serve.tick`` profiler span holding the engine's and the pool's
    spans (see ``repro.serve.engine``).
    """

    def __init__(
        self,
        engine: ServingEngine,
        classes: Sequence[SLAClass] = DEFAULT_CLASSES,
        interleave: Optional[InterleavePolicy] = None,
    ):
        if engine.admission != "prefill":
            raise ValueError(
                "ServeFrontend requires prefill admission: replay admission "
                "replays prompts through the decode jit and cannot overlap "
                "with in-flight decode ticks"
            )
        if engine.queue:
            raise ValueError(
                "engine already has queued requests; submit through the "
                "front end instead"
            )
        self.engine = engine
        self.scheduler = SLAScheduler(classes)
        self.interleave = interleave or InterleavePolicy()
        engine.requeue_hook = self.scheduler.requeue
        engine.victim_hook = self.scheduler.pick_victim
        self._streams: Dict[int, TokenStream] = {}
        self._emitted: Dict[int, int] = {}
        # un-landed double-buffered tick: (sampled (B,1) device array,
        # dispatch-time active mask).  At most one — tick N+1's dispatch
        # lands tick N in the same tick() call.
        self._inflight = None
        self.stats: Dict[str, int] = {
            "ticks": 0, "chained": 0, "host_dispatch": 0,
        }

        # fresh-slot token merge for chained dispatch: where admission
        # wrote a newer host token than the device chain, take the host's.
        # A nested def: each front end keeps its own jit (and compile
        # count), under the stable program name ``jit_merge_tokens_fn``.
        def merge_tokens_fn(fresh, host, chain):
            return jnp.where(fresh[:, None], host, chain)

        mesh = engine.mesh
        if mesh is None:
            self._merge_toks = jax.jit(merge_tokens_fn)
        else:
            repl = engine._repl
            self._merge_toks = jax.jit(
                merge_tokens_fn,
                in_shardings=(repl, repl, repl),
                out_shardings=repl,
            )
        # bound 1 (+1 mesh signature slack): fixed (B,) bool + two (B, 1)
        # int32 inputs — the same tick-invariant shapes as the decode jit.
        slack = 1 if mesh is not None else 0
        engine.compile_guard.register(
            "merge_toks", self._merge_toks, 1 + slack
        )

    # ------------------------------------------------------------- intake
    def submit(
        self, req: Request, adapter: Optional[str] = None
    ) -> TokenStream:
        """Validate ``req``, queue it in its latency class, and return
        its :class:`TokenStream`.  ``req.arrival_time`` may be set to a
        FUTURE engine-clock time (open-loop load: the scheduler releases
        it when the clock reaches it); unset stamps now."""
        if req.uid in self._streams:
            raise ValueError(f"request uid {req.uid} already in flight")
        self.engine.validate(req, adapter)
        self.scheduler.submit(req)
        stream = TokenStream(req, self.engine.clock)
        self._streams[req.uid] = stream
        self._emitted[req.uid] = 0
        return stream

    def pending(self) -> bool:
        return (
            self.scheduler.pending()
            or any(s is not None for s in self.engine.slots)
            or self.engine._chunking is not None
            or self._inflight is not None
        )

    # ------------------------------------------------------ double buffer
    def _chain_safe(self) -> bool:
        """True when EVERY slot active in the un-landed tick provably
        survives it: no ``eos_id`` (any sampled token could be EOS),
        token budget and ``max_len`` headroom for one more tick after
        the pending one.  Paged capacity is checked separately
        (:meth:`_ensure_chain`) after admission."""
        if self._inflight is None:
            return False
        eng = self.engine
        _, act = self._inflight
        for i in range(eng.n_slots):
            if not act[i]:
                continue
            req = eng.slots[i]
            if req is None or req.eos_id is not None:
                return False
            if len(req.output) + 1 >= req.max_new_tokens:
                return False
            if int(eng._lengths[i]) + 1 >= eng.max_len - 1:
                return False
        return True

    @functools.partial(jax.profiler.annotate_function, name="serve.grow")
    def _ensure_chain(self, active: np.ndarray) -> bool:
        """Reserve paged blocks for BOTH pending tokens of a chained
        dispatch: an in-flight slot lands one token and immediately
        decodes another (capacity ``len+2``); a freshly admitted slot
        only decodes (``len+1``).  Returns False — fall back to the
        synchronous land-then-dispatch path — if any arena is exhausted
        (blocks already granted stay reserved; a completing victim
        releases them, and the fallback's ``_ensure_growth`` preempts
        through the same arenas otherwise)."""
        eng = self.engine
        if not eng._paged:
            return True
        _, act = self._inflight
        try:
            for i in range(eng.n_slots):
                if act[i]:
                    eng.pager.ensure(i, int(eng._lengths[i]) + 2)
                elif active[i]:
                    eng.pager.ensure(i, int(eng._lengths[i]) + 1)
        except MemoryError:
            return False
        return True

    def _land_inflight(self) -> None:
        """Land the un-landed tick and clear the in-flight buffer."""
        inflight, self._inflight = self._inflight, None
        self._land(*inflight)

    def _land(self, sampled, act: np.ndarray) -> None:
        """Fetch a tick's sampled tokens (the ONE D2H copy per tick) and
        run the engine's postprocess under its dispatch-time active
        mask."""
        eng = self.engine
        with jax.profiler.TraceAnnotation("serve.land"):
            eng._postprocess(eng._fetch(sampled)[:, 0], act)

    def _dispatch(self, active: np.ndarray, chained: bool) -> None:
        """Dispatch one decode tick from the host tokens (``chained``:
        from the in-flight tick's sampled tokens, the host's where a
        slot was admitted since) and hold its sampled tokens as the new
        in-flight buffer (device future — no host sync here)."""
        eng = self.engine
        with jax.profiler.TraceAnnotation("serve.dispatch",
                                          chained=int(chained)):
            toks = jnp.asarray(eng._last_token.reshape(-1, 1))
            if chained:
                toks = self._merge_toks(
                    jnp.asarray(eng._fresh), toks, self._inflight[0]
                )
            logits = eng.dispatch_decode(toks, active)
            self._inflight = (eng._sample(logits), active.copy())

    # ------------------------------------------------------------- tick
    def tick(self) -> bool:
        """One front-end scheduling tick: chunk burst, EDF admission,
        chained-or-host decode dispatch, then land the previous tick.
        Returns True when any work was done (False = idle: nothing
        ready before the next scheduled arrival)."""
        eng = self.engine
        live = sum(s is not None for s in eng.slots)
        with jax.profiler.TraceAnnotation("serve.tick",
                                          tick=self.stats["ticks"],
                                          live=live):
            t0 = eng.clock()
            did = False

            # 1) chunked-prefill burst per the interleave policy
            if eng._chunking is not None:
                decoding = (
                    self._inflight is not None
                    or any(s is not None for s in eng.slots)
                )
                cls = self.scheduler.classes.get(
                    eng._chunking["req"].latency_class
                )
                steps = self.interleave.chunk_steps(
                    decoding, cls.priority if cls is not None else None
                )
                for _ in range(steps):
                    if eng._chunking is None:
                        break
                    eng._step_chunked()
                    did = True

            # 2) land BEFORE admission when the un-landed tick may complete
            # a request — its freed slots then admit this very tick.
            if self._inflight is not None and not self._chain_safe():
                self._land_inflight()
                did = True

            # 3) EDF admission from the scheduler's ready view
            eng._admit(queue=self.scheduler.view(eng.clock()), chunk=False)
            active = np.array([s is not None for s in eng.slots])

            # 4) decode dispatch: chained (double-buffered) when safe
            if self._inflight is not None:
                if self._ensure_chain(active):
                    old = self._inflight
                    self._dispatch(active, chained=True)
                    self.stats["chained"] += 1
                    # the older tick lands while the new dispatch runs
                    self._land(*old)
                    did = True
                else:
                    # arena full: land (chain-safe held, so nothing
                    # completes), preempt through the SLA victim hook, and
                    # dispatch synchronously from host tokens.
                    self._land_inflight()
                    active = np.array([s is not None for s in eng.slots])
                    eng._ensure_growth(active)
                    if active.any():
                        self._dispatch(active, chained=False)
                        self.stats["host_dispatch"] += 1
                        did = True
            elif active.any():
                if eng._paged:
                    eng._ensure_growth(active)
                if active.any():
                    self._dispatch(active, chained=False)
                    self.stats["host_dispatch"] += 1
                    did = True

            # 5) stream landed tokens; refresh gauges
            self._flush_streams()
            self.stats["ticks"] += 1
            depths = self.scheduler.depths()
            eng.stats["queue_depth"] = depths
            peak = eng.stats.setdefault("queue_depth_peak", {})
            for name, depth in depths.items():
                peak[name] = max(peak.get(name, 0), depth)
            if did:
                eng.tick_hist.record(max(eng.clock() - t0, 0.0))
        if sanitize.enabled():
            eng.compile_guard.assert_ok()
        return did

    @functools.partial(jax.profiler.annotate_function, name="serve.stream")
    def _flush_streams(self) -> None:
        """Push every landed-but-unstreamed token to its stream; close
        and retire streams whose requests completed."""
        finished = []
        for uid, stream in self._streams.items():
            req = stream.request
            sent = self._emitted[uid]
            for tok in req.output[sent:]:
                stream._push(tok)
            self._emitted[uid] = len(req.output)
            if req.done:
                stream._close()
                finished.append(uid)
        for uid in finished:
            del self._streams[uid]
            del self._emitted[uid]

    # ------------------------------------------------------------- loops
    def _idle(self) -> None:
        """Nothing ready: wait for the next scheduled arrival (advance a
        virtual clock directly; nap a real one)."""
        nxt = self.scheduler.next_arrival()
        if nxt is None:
            return
        clk = self.engine.clock
        if isinstance(clk, VirtualClock):
            if nxt > clk.now:
                clk.now = nxt
        else:
            time.sleep(min(max(nxt - clk(), 0.0), 0.001))

    def drain(self, max_ticks: int = 100_000) -> None:
        """Run ticks until every submitted request has completed (the
        synchronous driver — threads/benchmarks; tests with a virtual
        clock drive :meth:`tick` directly)."""
        ticks = 0
        while self.pending() and ticks < max_ticks:
            if not self.tick():
                self._idle()
            ticks += 1
        if self._inflight is not None:
            self._land_inflight()
            self._flush_streams()

    async def serve(self, max_ticks: int = 100_000) -> None:
        """Async driver: same loop as :meth:`drain` but yields to the
        event loop every tick so ``async for tok in stream`` consumers
        interleave with the scheduler."""
        ticks = 0
        while self.pending() and ticks < max_ticks:
            busy = self.tick()
            if not busy:
                self._idle()
            await asyncio.sleep(0)
            ticks += 1
        if self._inflight is not None:
            self._land_inflight()
            self._flush_streams()
