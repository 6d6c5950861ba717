"""Find an open-loop cell's knee: the highest rate with no growing backlog.

    python3 bench/sweep.py --workload siot-gcn.poisson --rates 2,3,4,5 \
        --seconds 30 --seed 11

Builds and warms the cell once, then offers each rate's Poisson stream in
turn (``bench/traffic.py``) and prints one line per rate: the rate offered
and completed, the median and 90th percentile latency, and the mean
latency of the last third of the requests over that of the first third,
which stays near 1 below the knee and grows with the backlog above it.
Needs the chip, as ``bench/run.py`` does. The knee it finds is written
into the traffic file by hand, at four fifths, and recorded in PERF.md.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def sweep(cell, rates, seconds, seed, devices=None):
    import numpy as np

    from bench import harness, stats, traffic

    harness.use_compile_cache(ROOT)
    b = harness.build(cell, seed)
    b.sizes = []
    serve = harness.serve_fn(b)
    for n in harness.batch_sizes(cell, b.max_batch):
        serve(list(range(n)))
    rows = []
    for rate in rates:
        b.sizes.clear()
        due = traffic.poisson_due(rate, seconds, seed)
        run = traffic.run_open(due, b.max_batch, serve)
        lat = (run.done - run.due) * 1e3
        third = max(1, len(lat) // 3)
        rows.append({
            "rate_rps": rate,
            "completed_rps": len(lat) / float(np.max(run.done)),
            "p50_ms": stats.percentile(lat, 50),
            "p90_ms": stats.percentile(lat, 90),
            "growth": float(np.mean(lat[-third:]) / np.mean(lat[:third])),
            "mean_batch": float(np.mean(b.sizes)),
            "requests": len(lat)})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.load_cell(args.workload, ROOT)
    try:
        harness.look_for_chip(cell.chips)
    except harness.NoChip as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 2
    sweep(cell, [float(r) for r in args.rates.split(",")], args.seconds,
          args.seed)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
