"""What every cell shares: arguments, the device check, the compile
cache, the files a cell is made of, per-layer metric readers, and the
result line."""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".out" / "jax_cache"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(bench_file)
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"unknown workload {name!r}")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{wl['traffic']}.json")

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    return Cell(name, wl["chips"], config, traffic, e2e, per_layer)


def require_device(chips: int):
    """The chips this cell asks for, or exit without a result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); there is no CPU fallback")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chip(s), JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, holding every program however quickly it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r}")
    return table[kind]


class Spans:
    """Host spans around the benchmark's own calls into the program:
    profiler annotations while tracing, nothing otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    config: dict
    traffic: dict
    peaks: dict
    counters: Dict[str, Any]
    trace: Any = None
    window_s: float = 0.0


def load_reader(name: str) -> Callable[[Context], Optional[float]]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, ctx: Context) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def emit(result: dict, compared: Dict[str, dict]) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, then the result line (``compared`` last) as the last
    line of standard output."""
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                          for k, v in compared.items()}
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    """A progress line on standard error, with device memory in use."""
    import jax

    used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.local_devices()]
    print(f"[bench] {msg} (bytes_in_use={max(used)})", file=sys.stderr,
          flush=True)


class Clock:
    """Seconds since the process started (set-up is measured from here)."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def __call__(self) -> float:
        return time.perf_counter() - self.t0
