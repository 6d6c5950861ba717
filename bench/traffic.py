"""Load generation: arrival schedules, upload pools and the two loops.

A traffic file (``bench/traffic/<mix>.json``) says which loop runs:

  * ``{"loop": "open", "arrivals": "poisson", "rate_rps": r,
    "order_seed": o, ...}``: independent users. Requests fall due on a
    schedule in real time, whatever the server is doing; each is timed
    from when it was due. The schedule belongs to the mix: ``o`` orders
    its gaps, so every run offers the same arrivals (``due_times``).
  * ``{"loop": "closed", "clients": c, ...}``: c clients, each with one
    request outstanding, which sends its next one when the answer comes.

and how many uploads are drawn: ``"uploads": {"pool": p}`` makes p uploads
from the seed before the window, by the configuration's upload rule
(``upload_pool``); request k carries upload ``k % p``.

The loops drive a ``serve(ids) -> answers`` callable, which submits the
requests to the server and drains it, so they run without a chip in tests.
"""
from __future__ import annotations

import collections
import time
from typing import Callable, List, NamedTuple, Sequence

import numpy as np

from bench import graphs


class Batch(NamedTuple):
    start: float        # seconds since the loop began
    end: float
    ids: Sequence[int]  # request ids, oldest first


class Run(NamedTuple):
    due: np.ndarray     # [n] when each request fell due (open loop), s
    done: np.ndarray    # [n] when its answer was in hand, s
    batches: List[Batch]
    lateness: np.ndarray  # how late the generator woke for a due request
    answers: dict       # request id -> answer


def poisson_due(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times of a Poisson stream of ``rate`` requests/s over
    ``seconds``, with the same set of gaps for every seed.

    The gaps are the exponential distribution's quantiles at
    (i + 1/2) / n for the n = round(rate * seconds) requests, so every seed
    offers the same load in the same count; the seed only orders them.
    The first request falls due at 0 and the last before ``seconds``."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def due_times(mix: dict, seconds: float) -> np.ndarray:
    """The open-loop mix's schedule over ``seconds``: the same for every
    run, whatever its seed. The order of the gaps sets the bursts that a
    tail latency sees, so a seed that ordered them would change the work
    from run to run; the run's seed draws the weights and the uploads."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"no arrivals {mix['arrivals']!r}")
    return poisson_due(float(mix["rate_rps"]), seconds,
                       int(mix["order_seed"]))


def upload_pool(stored: np.ndarray, pool: int, rule: dict,
                seed: int) -> np.ndarray:
    """[pool, V, F] uploads from the seed, each one distinct, drawn by the
    configuration's upload rule:

      * ``{"rule": "noise", "sigma": s}``: the stored features plus s
        times standard normal noise (dense features, as Yelp's);
      * ``{"rule": "redraw", "share": q}``: one-hot blocks (SIoT's device
        attributes) in which a share q of the vertices, drawn anew for
        each upload, report a new category in every block; the rest
        report what is stored. The uploads stay one-hot."""
    rng = np.random.default_rng(seed)
    base = np.asarray(stored, np.float32)
    n, dim = base.shape
    out = np.empty((pool,) + base.shape, np.float32)
    for k in range(pool):
        if rule["rule"] == "noise":
            out[k] = base + np.float32(rule["sigma"]) * rng.standard_normal(
                base.shape, dtype=np.float32)
        elif rule["rule"] == "redraw":
            out[k] = base
            rows = rng.choice(n, size=int(round(rule["share"] * n)),
                              replace=False)
            for first, width in graphs.onehot_blocks(dim):
                out[k][rows, first:first + width] = 0.0
                cat = rng.integers(0, width, size=len(rows))
                out[k][rows, first + cat] = 1.0
        else:
            raise ValueError(f"no upload rule {rule['rule']!r}")
    return out


def run_open(due: np.ndarray, max_batch: int,
             serve: Callable[[List[int]], list],
             clock: Callable[[], float] = time.perf_counter,
             sleep: Callable[[float], None] = time.sleep) -> Run:
    """Whenever the server is free, hand it the oldest due requests (at
    most ``max_batch``); sleep when none is due. Runs until every
    request of ``due`` is answered, so the backlog at the end of the
    window is served and timed, not dropped."""
    n = len(due)
    done = np.full(n, np.nan)
    batches: List[Batch] = []
    late: List[float] = []
    answers = {}
    t0 = clock()
    i = 0
    while i < n:
        now = clock() - t0
        if due[i] > now:
            sleep(due[i] - now)
            now = clock() - t0
            late.append(now - due[i])
        j = i
        while j < n and j - i < max_batch and due[j] <= now:
            j += 1
        ids = list(range(i, j))
        for k, a in zip(ids, serve(ids)):
            answers[k] = a
        end = clock() - t0
        done[i:j] = end
        batches.append(Batch(now, end, ids))
        i = j
    return Run(np.asarray(due, float), done, batches, np.asarray(late),
               answers)


def run_closed(clients: int, max_batch: int, seconds: float,
               serve: Callable[[List[int]], list],
               clock: Callable[[], float] = time.perf_counter) -> Run:
    """``clients`` clients with one request outstanding each. The server
    takes the oldest outstanding requests (at most ``max_batch``); each
    answer sends that client's next request. No batch starts after
    ``seconds``; the requests still outstanding then were never sent to
    the server and are not part of the run."""
    pending = collections.deque(range(clients))
    nxt = clients
    batches: List[Batch] = []
    answers = {}
    t0 = clock()
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        ids = [pending.popleft()
               for _ in range(min(max_batch, len(pending)))]
        for k, a in zip(ids, serve(ids)):
            answers[k] = a
        batches.append(Batch(now, clock() - t0, ids))
        pending.extend(range(nxt, nxt + len(ids)))
        nxt += len(ids)
    served = sum(len(b.ids) for b in batches)   # ids 0 .. served - 1
    done = np.full(served, np.nan)
    for b in batches:
        done[list(b.ids)] = b.end
    return Run(np.zeros(served), done, batches, np.zeros(0), answers)
