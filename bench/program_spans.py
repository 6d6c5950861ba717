"""The program's own spans in a profiler trace, and what they measure.

The program names its layers with ``repro.<name>`` annotations
(``src/repro/runtime/tracing.py``); ``bench/trace.py`` keeps only the
benchmark's ``bench.<name>`` spans. This module reads the program's spans
from the same ``.xplane.pb``, on the same clock:

  * each span keeps its counters (the event's stats) and is keyed by its
    path: the names of the program spans around it on its thread, from
    the outermost in, joined by ``/`` (``server.drain/server.batch/
    collect/daq.lossless``);
  * ``totals`` sums seconds, count and counters by path;
  * ``reduce`` is ``bench.trace.reduce`` with the program spans beside the
    benchmark's, so each idle stretch goes to the innermost of either: a
    program span by its path, a benchmark span by its short name, and
    ``harness`` where neither is open;
  * ``per_layer`` reads six per-layer numbers from the totals.

    python3 bench/program_spans.py <trace dir | .xplane.pb[.gz]> [chips]

prints the totals, the six numbers and the idle breakdown as one JSON
object.
"""
from __future__ import annotations

import collections
import gzip
import json
import os
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PREFIX = "repro."


class Span(NamedTuple):
    path: str
    start: float   # seconds from the start of the trace
    end: float
    stats: Dict[str, int]


class Spent(NamedTuple):
    seconds: float
    count: int
    stats: Dict[str, int]   # each counter summed over the spans


def _profile(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def nest(events: Iterable) -> List[Span]:
    """Spans of one thread, each keyed by its path. ``events`` carry
    ``name`` (without the prefix), ``start``, ``end`` and ``stats``; an
    event opened inside another on the thread closes inside it too."""
    out: List[Span] = []
    open_: List[Span] = []
    for name, s, e, stats in sorted(events, key=lambda x: (x[1], -x[2])):
        while open_ and open_[-1].end <= s:
            open_.pop()
        path = f"{open_[-1].path}/{name}" if open_ else name
        out.append(Span(path, s, e, stats))
        open_.append(out[-1])
    return out


def read_spans(path: str) -> List[Span]:
    """Every program span of one trace (an ``.xplane.pb`` file or a
    gzipped one), thread by thread."""
    spans: List[Span] = []
    for plane in _profile(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend(nest(
                (e.name[len(PREFIX):], e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9,
                 {k: v for k, v in e.stats if isinstance(v, int)})
                for e in line.events if e.name.startswith(PREFIX)))
    return spans


def totals(spans: Iterable[Span]) -> Dict[str, Spent]:
    secs: Dict[str, float] = collections.defaultdict(float)
    count: Dict[str, int] = collections.defaultdict(int)
    stats: Dict[str, Dict[str, int]] = collections.defaultdict(
        lambda: collections.defaultdict(int))
    for sp in spans:
        secs[sp.path] += sp.end - sp.start
        count[sp.path] += 1
        for k, v in sp.stats.items():
            stats[sp.path][k] += v
    return {p: Spent(secs[p], count[p], dict(stats[p])) for p in secs}


def reduce(devices, bench_spans: List[trace.Event], spans: List[Span],
           window: float) -> trace.Reduction:
    """``bench.trace.reduce`` with the program spans given to the idle
    attribution too, named by their paths. Of spans that cover the same
    stretch exactly, the innermost is the deepest program span: the
    attribution takes the first of equals, so program spans go first,
    deepest first, and the benchmark's after them."""
    program = [trace.Event(sp.path, sp.start, sp.end)
               for sp in sorted(spans, key=lambda sp: -sp.path.count("/"))]
    return trace.reduce(devices, program + list(bench_spans), window)


def load(path: str, chips: int = 1):
    """(reduction with program paths in its idle breakdown, totals by
    path) of the trace under ``path``, a directory or a file, over the
    first ``chips`` chips."""
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    devices, bench_spans, window = trace.read_events(path)
    spans = read_spans(path)
    red = reduce({c: evs for c, evs in devices.items() if c < chips},
                 bench_spans, spans, window)
    return red, totals(spans)


def _ends(path: str, tail: str) -> bool:
    return path == tail or path.endswith("/" + tail)


def per(tot: Dict[str, Spent], tails: Sequence[str], unit: str,
        stat: Optional[str] = None) -> Optional[float]:
    """The seconds (or the summed counter ``stat``) of the spans whose
    path ends with one of ``tails``, per ``unit``: the count of spans
    whose path ends with ``unit`` (``server.batch`` for a batch,
    ``collect`` for a request). None where the trace holds neither."""
    n = sum(s.count for p, s in tot.items() if _ends(p, unit))
    hit = [s for p, s in tot.items() if any(_ends(p, t) for t in tails)]
    if not n or not hit:
        return None
    if stat is None:
        return sum(s.seconds for s in hit) / n
    return sum(s.stats.get(stat, 0) for s in hit) / n


def per_layer(tot: Dict[str, Spent]) -> Dict[str, Optional[float]]:
    """Six per-layer numbers of the serving path: pricing per batch, the
    upload codec's stages per request, the executor's dispatch and wait
    per batch, and the megabytes it copies to the device per batch."""
    def ms(tails, unit):
        v = per(tot, tails, unit)
        return None if v is None else 1e3 * v

    upload = per(tot, ["execute/execute.dispatch"], "server.batch",
                 "upload_bytes")
    return {
        "price_ms": ms(["server.price"], "server.batch"),
        "lossless_ms": ms(["collect/daq.lossless"], "collect"),
        "daq_ms": ms(["collect/daq.quantize", "collect/daq.dequantize"],
                     "collect"),
        "dispatch_ms": ms(["execute/execute.dispatch"], "server.batch"),
        "wait_ms": ms(["execute/execute.wait"], "server.batch"),
        "upload_mb": None if upload is None else upload / 1e6,
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    red, tot = load(argv[0], int(argv[1]) if len(argv) > 1 else 1)
    print(json.dumps({
        "totals": {p: s._asdict() for p, s in sorted(tot.items())},
        "per_layer": per_layer(tot),
        "breakdown": trace.breakdown(red)}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
