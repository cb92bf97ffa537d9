"""Reduction of a profiler trace to what the per-layer metrics read.

Reads the ``.xplane.pb`` the JAX profiler wrote, with nothing but JAX:

* device busy time: the union of the intervals in which an operation ran
  on each chip (overlapping operations count once), averaged over chips;
* the traced window: the benchmark's own ``bench.window`` host span;
* time and count of each Mosaic kernel (by its ``pallas_call`` name) and
  of each XLA module (a jitted program, by its ``jit_<function>`` name),
  optionally only the kernels running inside one module;
* the longest idle gaps of the device, each attributed to the host span
  of the benchmark that overlaps it most (``untracked`` where none does).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_KERNEL = re.compile(r"(\w+)\)*/pallas_call")
_SUFFIX = re.compile(r"\.\d+$")


@dataclass
class Event:
    name: str
    start: float     # ns
    dur: float       # ns
    label: str = ""  # name and every string stat, for kernel matching

    @property
    def end(self) -> float:
        return self.start + self.dur


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by ``[(start, end)]``; overlaps count once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """Maximal sub-intervals of ``[lo, hi]`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def op_name(ev: Event) -> str:
    """The HLO instruction an op event ran, without its ``%`` and
    numeric suffix: ``%paged_decode.9 = bf16[...] custom-call(...)``
    gives ``paged_decode`` (a Mosaic kernel's instruction carries its
    ``pallas_call`` name), ``%fusion.12`` gives ``fusion``."""
    head = ev.name.split(" ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


def kernel_of(ev: Event) -> Optional[str]:
    m = _KERNEL.search(ev.label)
    return m.group(1) if m else op_name(ev)


def self_times(evs: List[Event]) -> List[float]:
    """Each op's time minus the time of the ops nested in it (a
    ``while`` holds its body's ops), so that no time counts twice."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i].start, -evs[i].dur))
    own = [e.dur for e in evs]
    stack: List[int] = []
    for i in order:
        e = evs[i]
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= evs[stack[-1]].end + 1:
            own[stack[-1]] -= e.dur
        stack.append(i)
    return own


@dataclass
class TraceSummary:
    window: Tuple[float, float]
    ops: Dict[int, List[Event]]            # chip -> op events
    modules: Dict[int, List[Event]]        # chip -> module events
    host: List[Event] = field(default_factory=list)

    def _clip(self, evs):
        lo, hi = self.window
        return [e for e in evs if e.end > lo and e.start < hi]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        lo, hi = self.window
        per_chip = [
            union_length([(max(e.start, lo), min(e.end, hi))
                          for e in self._clip(evs)])
            for evs in self.ops.values()
        ]
        return sum(per_chip) / max(len(per_chip), 1) * 1e-9

    def module_time(self, fn: str) -> Tuple[float, int]:
        """Seconds and executions of the jitted function ``fn`` on chip 0
        within the window."""
        evs = [e for e in self._clip(self.modules.get(0, []))
               if module_name(e.name) == f"jit_{fn}"]
        return sum(e.dur for e in evs) * 1e-9, len(evs)

    def kernel_time(self, kernel: str, module: Optional[str] = None
                    ) -> Tuple[float, int]:
        """Seconds and calls of a Mosaic kernel on chip 0 in the window,
        only inside executions of ``module`` if given."""
        evs = [e for e in self._clip(self.ops.get(0, []))
               if kernel_of(e) == kernel]
        if module is not None:
            spans = sorted((m.start, m.end)
                           for m in self._clip(self.modules.get(0, []))
                           if module_name(m.name) == f"jit_{module}")
            evs = [e for e in evs if _inside(e, spans)]
        return sum(e.dur for e in evs) * 1e-9, len(evs)

    def breakdown(self, top: int = 10) -> dict:
        lo, hi = self.window
        tot: Dict[str, float] = {}
        evs = self._clip(self.ops.get(0, []))
        for e, own in zip(evs, self_times(evs)):
            key = kernel_of(e)
            tot[key] = tot.get(key, 0.0) + own * 1e-9
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        busy = [(e.start, e.end) for e in self._clip(self.ops.get(0, []))]
        idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.attribute(s, e), (e - s) * 1e-9]
                              for s, e in idle]}

    def attribute(self, s: float, e: float) -> str:
        """The benchmark host span overlapping ``[s, e]`` most."""
        best, best_ov = "untracked", 0.0
        for h in self.host:
            if h.name == "bench.window":
                continue
            ov = min(e, h.end) - max(s, h.start)
            if ov > best_ov:
                best, best_ov = h.name, ov
        return best


def module_name(name: str) -> str:
    return name.split("(", 1)[0]


def _inside(ev: Event, spans) -> bool:
    import bisect

    i = bisect.bisect_right(spans, (ev.start, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= ev.start and ev.end <= spans[i][1] + 1


def _label(ev) -> str:
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def from_profile(pd) -> Optional[TraceSummary]:
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [Event(e.name, e.start_ns, e.duration_ns,
                                       _label(e)) for e in line.events]
                elif line.name == "XLA Modules":
                    modules[chip] = [Event(e.name, e.start_ns, e.duration_ns)
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append(Event(e.name, e.start_ns, e.duration_ns))
    if not any(ops.values()):
        return None
    win = [h for h in host if h.name == "bench.window"]
    if win:
        window = (win[0].start, win[0].end)
    else:
        allops = [e for evs in ops.values() for e in evs]
        window = (min(e.start for e in allops), max(e.end for e in allops))
    return TraceSummary(window, ops, modules, host)


def reduce(trace_dir: str) -> Optional[TraceSummary]:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return None
    return from_profile(ProfileData.from_file(files[-1]))
