#!/usr/bin/env python3
"""Find an open-loop serving mix's knee once: the highest offered rate at
which the backlog stays bounded and 90% of requests meet their class's
TTFT target.

    python3 bench/sweep.py --config minicpm-2b --traffic tenants \
        --seed <n> --seconds <per rate> --rates 1,2,3,4

One process builds and warms the engine once, then offers the mix at
each rate in turn (draining in between) and prints one JSON line per
rate.  The rate a cell runs at is written into its traffic file by hand
from this output; the benchmark's own runs never search for it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    from bench import generate, harness as H, serve as S

    config = H.load_json(H.BENCH / "configs" / f"{args.config}.json")
    mix = H.load_json(H.BENCH / "traffic" / f"{args.traffic}.json")
    cell = H.Cell(f"{args.config}.{args.traffic}", 1, config, mix, [], [])
    H.require_device(cell.chips)
    H.enable_compile_cache()
    from repro.serve.scheduler import DEFAULT_CLASSES

    targets = {c.name: c.ttft_target for c in DEFAULT_CLASSES}
    front, specs = S.build_engine(cell.config, args.seed)
    spans = H.Spans(False)
    S.warm_up(front, cell.config, cell.traffic, specs, spans)
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = json.loads(json.dumps(cell.traffic))
        traffic["arrivals"]["rate"] = rate
        reqs = generate.serve_requests(traffic, cell.config["vocab_size"],
                                       args.seed, len(specs), args.seconds)
        t0, end, streams, counters = S.run_window(
            front, reqs, specs, args.seconds, spans)
        # backlog at the close: due requests not yet given a first token
        lat = S.latency_metrics(t0, end, streams, counters)
        late_at_close = sum(1 for r, s in streams
                            if not s.token_times or s.token_times[0] > end)
        met = sum(1 for (r, s), t in zip(streams, lat["ttft"])
                  if t <= targets[r.latency_class])
        # finish what is in flight before the next rate (at most 2 min)
        stop = time.monotonic() + 120
        while front.pending() and time.monotonic() < stop:
            if not front.tick():
                time.sleep(0.0005)
        print(json.dumps({
            "rate": rate, "due": len(streams),
            "attainment": met / max(len(streams), 1),
            "unserved_at_close": late_at_close,
            "ttft_p50_ms": 1e3 * float(sorted(lat["ttft"])[len(lat["ttft"]) // 2]),
            "ttft_p95_ms": 1e3 * S.p95(lat["ttft"]),
            "itl_p95_ms": 1e3 * S.p95(lat["gaps"]) if lat["gaps"] else None,
            "tokens_per_s": lat["landed"] / args.seconds,
            "adapter_loads": counters["adapter_loads"],
            "preemptions": front.engine.stats.get("preemptions", 0),
            "ttft_s": [round(x, 3) for x in lat["ttft"]],
        }), flush=True)
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
