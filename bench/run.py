"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload siot-gcn.poisson --seed 7 --seconds 40 \
        --trace 0

Run from the root of a checkout. The cell (``--workload``) is an entry of
``BENCHMARK.json``; ``--trace 0`` reports its end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiled run. The last line of standard
output is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (and ``breakdown`` when traced), then the
numbers compared against the reference with their limits under
``checks``; those numbers are also the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench/run.py: no program at {ROOT}/src/repro",
              file=sys.stderr)
        return 1
    from bench import harness, spec

    cell = spec.load_cell(args.workload, ROOT)
    try:
        devices = harness.look_for_chip(cell.chips)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices=devices,
                              t_start=T_START, root=ROOT)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
