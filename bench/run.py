#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration and
traffic mix are files under ``bench/configs`` and ``bench/traffic``, its
per-layer metrics readers under ``bench/metrics``.  Exits non-zero, with
no result, when JAX finds no TPU or fewer chips than the cell asks for.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), then ``compared``: each number the check
compared with its limit.
"""

from __future__ import annotations

import importlib
import shutil
import sys
import time
from pathlib import Path

_T0 = time.perf_counter()
_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def main(argv=None) -> int:
    from bench import harness as H

    clock = H.Clock()
    clock.t0 = _T0
    args = H.parse_args(argv)
    cell = H.load_cell(args.workload)
    devices = H.require_device(cell.chips)
    import repro  # noqa: F401  (the system under test must be present)

    H.enable_compile_cache()
    peaks = H.peaks_for(devices[0].device_kind)
    trace_dir = None
    if args.trace:
        trace_dir = str(H.BENCH / ".out" / "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    spans = H.Spans(bool(args.trace))
    # the mix's kind names the module that runs it (bench/<kind>.py)
    cell_kind = importlib.import_module(f"bench.{cell.traffic['kind']}")
    result, compared = cell_kind.run(cell, args, clock, devices, spans,
                                     peaks, trace_dir)
    H.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
