# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one entry per paper artifact:

    param_efficiency  -> Tables 2-4 "# Params (%)" columns (exact analytic)
    rte_proxy         -> Table 1 (low-intrinsic-rank task parity)
    drop_proxy        -> Table 2 / Fig. 4 (high-rank task, methods sweep)
    subspace          -> Fig. 2 / App. A (intrinsic-rank diagnostic)
    commonsense_proxy -> Tables 3-4 (joint multi-task fine-tuning)
    kernel_bench      -> Limitations section (fused chain vs sequential)
    attention_bench   -> §Perf flash-attention kernel vs reference path
                         (seq-len/window/GQA sweeps, visible-block ratio)
    roofline          -> EXPERIMENTS.md roofline table from dry-run records
    serve_bench       -> §6 zero-overhead serving: replay vs prefill-wave
                         admission latency + tokens/sec per model family,
                         plus dense vs paged KV-cache rows (block-pool
                         cache gauges; paged asserted token-identical)
"""

import sys
import traceback


def main() -> None:
    from benchmarks import (
        attention_bench,
        commonsense_proxy,
        drop_proxy,
        fig4_sweep,
        kernel_bench,
        param_efficiency,
        roofline,
        rte_proxy,
        serve_bench,
        subspace,
    )

    print("name,us_per_call,derived")
    failures = []
    for mod in (param_efficiency, rte_proxy, drop_proxy, fig4_sweep,
                subspace, commonsense_proxy, kernel_bench, attention_bench,
                roofline, serve_bench):
        try:
            mod.main()
        except Exception as e:  # noqa: BLE001
            failures.append((mod.__name__, repr(e)))
            traceback.print_exc()
    if failures:
        print(f"benchmarks/FAILURES,0,{failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
