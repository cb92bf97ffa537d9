"""What the program's own tracing leaves in a profile, for the per-layer
metrics that read it:

* the name scopes its ops run under (``quanta`` around the QuanTA chain,
  ``adapter`` around a bank's per-request adapter work).  A TPU op event
  names only its HLO instruction; the instruction's name stack is its
  ``op_name`` metadata in the optimized module, which the profiler
  stores in the profile's ``/host:metadata`` plane (``hlo_op_names``).
  ``scope_time`` sums the self time of the ops under a scope;
* its host spans (``serve.*``, ``pool.*``), which the trace reduction
  leaves out (it keeps the benchmark's own ``bench.*`` spans,
  ``bench/trace.py``), and the runtime's own waits (``RUNTIME_WAITS``).

The newest profile under ``TRACE_DIR`` is read once per process.  A
profile of a program without these spans or scopes reads as nothing
found (``None`` from the readers), never as an error.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import harness as H
from bench import trace as T

# the directory bench/run.py gives the profiler in a traced run
TRACE_DIR = H.BENCH / ".out" / "trace"
# a tick did work when it advanced a chunk, dispatched or landed a step
WORK_SPANS = ("serve.chunk", "serve.dispatch", "serve.land")
# where the runtime blocks the calling thread on the device: a dispatch
# waits here for memory that a running step still holds
RUNTIME_WAITS = ("AllocateBufferAwait",)


def scope_label(scope: str) -> re.Pattern:
    """A label naming ``scope`` as one component of an op's name stack,
    also where a transformation wraps it: ``.../adapter/...``,
    ``.../vmap(quanta)/...``, ``.../transpose(jvp(quanta))/...``."""
    return re.compile(rf"(?:^|[/;\s])(?:[\w.]+\()*{re.escape(scope)}\)*/")


def scope_time(summary: T.TraceSummary, module: str, scope: str,
               op_names: Dict[str, Dict[str, str]]) -> Tuple[float, int]:
    """Seconds and count of the ops on chip 0, in the window, inside
    executions of ``jit_<module>`` whose name stack carries ``scope``.
    Each execution's ops are looked up in its own compiled module's
    ``op_names`` (from ``hlo_op_names``, keyed by the module event's
    full name), and each op counts its self time, so an op nested in
    another counts once."""
    pat = scope_label(scope)
    runs = sorted((m.start, m.end, m.name)
                  for m in summary._clip(summary.modules.get(0, []))
                  if T.module_name(m.name) == f"jit_{module}")
    evs = summary._clip(summary.ops.get(0, []))
    secs, n = 0.0, 0
    for e, own in zip(evs, T.self_times(evs)):
        run = _run_of(e, runs)
        if run and pat.search(op_names.get(run, {}).get(instruction(e), "")):
            secs += own
            n += 1
    return secs * 1e-9, n


def _run_of(ev: T.Event, runs) -> Optional[str]:
    """The name of the execution in ``runs`` (sorted ``(start, end,
    name)``) that holds ``ev``, or None."""
    i = bisect.bisect_right(runs, (ev.start, float("inf"), "")) - 1
    if i >= 0 and runs[i][0] <= ev.start and ev.end <= runs[i][1] + 1:
        return runs[i][2]
    return None


def instruction(ev: T.Event) -> str:
    """The HLO instruction an op event ran: ``%fusion.12 = bf16[...]
    fusion(...)`` gives ``fusion.12``."""
    return ev.name.split(" ", 1)[0].lstrip("%")


def newest_profile(trace_dir: Optional[Path] = None) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir`` (default
    ``TRACE_DIR``), or None."""
    files = sorted(glob.glob(os.path.join(trace_dir or TRACE_DIR, "**",
                                          "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


@functools.lru_cache(maxsize=1)
def _read_profile(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def hlo_op_names(trace_dir: Optional[Path] = None
                 ) -> Dict[str, Dict[str, str]]:
    """Per compiled program (the full ``jit_<function>(<id>)`` name its
    module events carry), each HLO instruction's name stack (its
    ``op_name`` metadata), from the optimized modules the profiler
    stores in the profile's ``/host:metadata`` plane."""
    path = newest_profile(trace_dir)
    if path is None:
        return {}
    space = memoryview(_read_profile(path))
    out: Dict[str, Dict[str, str]] = {}
    for plane in _fields(space, 1):                   # XSpace.planes
        if bytes(_first(plane, 2)) != b"/host:metadata":
            continue
        # stat_metadata and event_metadata are maps: key 1, value 2
        hlo_stat = next((_int(_first(e, 1)) for e in _fields(plane, 5)
                         if _first(_first(e, 2), 2) == b"Hlo Proto"), None)
        for entry in _fields(plane, 4):
            meta = _first(entry, 2)                   # XEventMetadata
            program = bytes(_first(meta, 2)).decode()
            for stat in _fields(meta, 5):             # XStat
                if _int(_first(stat, 1)) == hlo_stat:
                    # XStat.bytes_value: an HloProto
                    out.setdefault(program, {}).update(
                        _module_op_names(_first(_first(stat, 6), 1)))
    return out


def _module_op_names(module) -> Dict[str, str]:
    """``instruction name -> op_name`` of a serialized HloModuleProto:
    computations (3) > instructions (2) > name (1), metadata (7) >
    op_name (2)."""
    out = {}
    for comp in _fields(module, 3):
        for ins in _fields(comp, 2):
            op = bytes(_first(_first(ins, 7), 2)).decode()
            if op:
                out[bytes(_first(ins, 1)).decode()] = op
    return out


def _varint(buf, i: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        out |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, number: int):
    """The values of field ``number`` of one serialized protobuf message
    (length-delimited fields as slices of ``buf``, varints as ints)."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        if key >> 3 == number:
            yield value


def _first(buf, number: int):
    return next(_fields(buf, number), b"")


def _int(value) -> int:
    return value if isinstance(value, int) else 0


def program_spans(window: Tuple[float, float],
                  trace_dir: Optional[Path] = None) -> List[T.Event]:
    """The program's host spans and the runtime's waits that lie wholly
    inside ``window`` (ns on the profile's clock), from the newest
    profile under ``trace_dir`` (default ``TRACE_DIR``)."""
    from jax.profiler import ProfileData

    path = newest_profile(trace_dir)
    if path is None:
        return []
    lo, hi = window
    out = []
    profile = ProfileData.from_serialized_xspace(_read_profile(path))
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if (e.name.startswith(("serve.", "pool."))
                        or e.name in RUNTIME_WAITS) and \
                        lo <= e.start_ns and \
                        e.start_ns + e.duration_ns <= hi:
                    out.append(T.Event(e.name, e.start_ns, e.duration_ns))
    return out


def host_work_ms(spans: List[T.Event]) -> Optional[float]:
    """Mean milliseconds of host work per ``serve.tick`` that did work:
    the tick's span less the time inside it that the host was blocked
    on the device, in the program's ``serve.wait`` spans or in the
    runtime's waits (a dispatch that waits for device memory), each
    instant counted once."""
    total, ticks = 0.0, 0
    for tick in (s for s in spans if s.name == "serve.tick"):
        inner = [s for s in spans if s is not tick
                 and tick.start <= s.start and s.end <= tick.end]
        if not any(s.name in WORK_SPANS for s in inner):
            continue
        total += tick.dur - T.union_length(
            [(s.start, s.end) for s in inner
             if s.name == "serve.wait" or s.name in RUNTIME_WAITS])
        ticks += 1
    return 1e-6 * total / ticks if ticks else None
