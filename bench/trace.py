"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it every TPU is a plane named ``/device:TPU:<i>``, whose ``XLA Ops``
line holds one event per operation that ran on it; the host plane
``/host:CPU`` holds the benchmark's ``bench.<name>`` annotations. All
events share one clock, in nanoseconds from the start of the trace.

What comes out (``Reduction``), each taken over the traced window:

  * ``busy_s``: per chip, the union of the intervals in which an
    operation ran;
  * ``ops_s``: device seconds by operation name, summed over chips;
  * ``idle_by_span``: the device's idle seconds (summed over chips), each
    stretch of idle time given to the innermost host span it falls in
    (``harness`` where the benchmark's loop was between spans);
  * ``window_s``: the length of the traced window.
"""
from __future__ import annotations

import bisect
import collections
import glob
import gzip
import os
import re
from typing import Dict, List, NamedTuple, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
#: Operation names of the neighbour-sum kernels (``pl.pallas_call`` takes
#: its name from the kernel function's).
SPMM_KERNEL = re.compile(r"spmm", re.IGNORECASE)
#: Operation names of collectives between chips (with their async
#: ``-start`` / ``-done`` halves).
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter|"
    r"send|recv)")


class Event(NamedTuple):
    name: str
    start: float   # seconds from the start of the trace
    end: float


class Reduction(NamedTuple):
    window_s: float
    busy_s: List[float]                 # per chip
    ops_s: Dict[str, float]             # by op name, summed over chips
    idle_by_span: Dict[str, float]      # summed over chips
    chips: int


def op_name(text: str) -> str:
    """An op event is named by its HLO text, ``%block_spmm.3 = f32[...]
    custom-call(...)``; keep the instruction's name, ``block_spmm.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files under "
                                f"{directory}; expected one")
    return paths[0]


def read_events(path: str) -> Tuple[Dict[int, List[Event]], List[Event],
                                    float]:
    """(device ops by chip, host spans, window seconds) of one trace, from
    an ``.xplane.pb`` file or a gzipped one (``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            prof = ProfileData.from_serialized_xspace(f.read())
    else:
        prof = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    window = None
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(Event(op_name(e.name), e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name[len(SPAN_PREFIX):],
                                   e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
        elif plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window = (st["profile_stop_time"]
                          - st["profile_start_time"]) * 1e-9
    if window is None:
        ends = [e.end for evs in devices.values() for e in evs]
        window = max(ends, default=0.0)
    return devices, spans, float(window)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: List[Tuple[float, float]], window: float
         ) -> List[Tuple[float, float]]:
    """The idle stretches of [0, window] between the busy intervals."""
    out, t = [], 0.0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window > t:
        out.append((t, window))
    return out


def attribute(stretch: Tuple[float, float], spans: List[Event]
              ) -> Dict[str, float]:
    """Split an idle stretch among the innermost host spans over it."""
    s0, s1 = stretch
    cuts = sorted({s0, s1} | {t for sp in spans for t in (sp.start, sp.end)
                              if s0 < t < s1})
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in zip(cuts[:-1], cuts[1:]):
        mid = 0.5 * (a + b)
        inner = [sp for sp in spans if sp.start <= mid < sp.end]
        name = (min(inner, key=lambda sp: sp.end - sp.start).name
                if inner else "harness")
        out[name] += b - a
    return out


def reduce(devices: Dict[int, List[Event]], spans: List[Event],
           window: float) -> Reduction:
    busy_s, ops = [], collections.defaultdict(float)
    idle = collections.defaultdict(float)
    spans = sorted(spans, key=lambda sp: sp.start)
    starts = [sp.start for sp in spans]
    longest = max((sp.end - sp.start for sp in spans), default=0.0)
    for chip in sorted(devices):
        evs = devices[chip]
        busy = union([(e.start, e.end) for e in evs])
        busy_s.append(sum(e - s for s, e in busy))
        for e in evs:
            ops[e.name] += e.end - e.start
        for g in gaps(busy, window):
            lo = bisect.bisect_left(starts, g[0] - longest)
            hi = bisect.bisect_left(starts, g[1])
            near = [sp for sp in spans[lo:hi] if sp.end > g[0]]
            for name, sec in attribute(g, near).items():
                idle[name] += sec
    return Reduction(window, busy_s, dict(ops), dict(idle), len(devices))


def load(directory: str, chips: int) -> Reduction:
    """The reduction of the trace under ``directory`` over the first
    ``chips`` chips, the ones the cell runs on."""
    devices, spans, window = read_events(find_xplane(directory))
    return reduce({c: evs for c, evs in devices.items() if c < chips},
                  spans, window)


def seconds_matching(r: Reduction, pattern: "re.Pattern") -> float:
    """Device seconds of the operations whose name matches, over chips."""
    return sum(s for name, s in r.ops_s.items() if pattern.search(name))


def breakdown(r: Reduction, top: int = 10) -> dict:
    ops = sorted(r.ops_s.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(r.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}
