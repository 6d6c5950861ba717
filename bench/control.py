"""Readings that set a cell's limit on ``rms_err``: the program's and the
control's, seed by seed, in one process.

    python3 bench/control.py --workload siot-gcn.poisson \
        --seeds 101,102,103

For each seed it builds the cell as a run does (weights and uploads from
the seed), serves every upload of the pool through the cell's own batch
sizes, and prints one line with the number the check compares,
``rms_err``:

  * ``program``: the answers against the reference at the precision the
    configuration states (a sound run's reading);
  * ``control``: the reference computed one precision lower
    (``reference.CONTROL``, with the configuration's halo wire), put in
    the program's place, which a limit has to fail;
  * ``no_wire`` (null where no wire is stated): the answers against the
    reference that sends the halo rows exact, which shows how much of
    ``program`` the modelled wire accounts for.

The benchmark's own runs do not run it. Needs the chip, as ``run.py``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def serve_pool(cell, seed, scale=None):
    """The built cell and its answers to every upload of the pool, served
    through the cell's own batch sizes."""
    from bench import harness

    b = harness.build(cell, seed, scale)
    b.sizes = []
    serve = harness.serve_fn(b)
    sizes = harness.batch_sizes(cell, b.max_batch)
    answers, k = {}, 0
    while k < len(b.pool):
        for n in sizes:
            ids = list(range(k, k + n))
            answers.update(zip(ids, serve(ids)))
            k += n
    return b, answers


def readings(cell, seed, platform="tpu", scale=None):
    """The program's and the control's ``rms_err`` on one seed, and the
    program's against the reference without the wire."""
    import numpy as np

    from bench import harness, reference

    b, answers = serve_pool(cell, seed, scale)
    params = [{n: np.asarray(v) for n, v in p.items()} for p in b.params]
    precs = reference.stated(cell.config, platform)
    cross = harness.wire(b, precs)
    low_prec = reference.CONTROL._replace(halo=precs[0].halo)
    prog = ctl = 0.0
    no_wire = None if cross is None else 0.0
    for p in range(len(b.pool)):
        x = reference.daq(b.pool[p], b.edges.degree)
        refs = [reference.forward(b.kind, params, b.edges, x, prec, cross)
                for prec in precs]
        low, _ = reference.forward(b.kind, params, b.edges, x, low_prec,
                                   cross)
        ctl = max(ctl, min(reference.scaled_rms(low, *r) for r in refs))
        exact = (None if cross is None else
                 [reference.forward(b.kind, params, b.edges, x, prec)
                  for prec in precs])
        for k, got in answers.items():
            if k % len(b.pool) == p:
                prog = max(prog, min(reference.scaled_rms(got, *r)
                                     for r in refs))
                if exact is not None:
                    no_wire = max(no_wire, min(
                        reference.scaled_rms(got, *r) for r in exact))
    return {"seed": seed, "answers": len(answers), "program": prog,
            "control": ctl, "no_wire": no_wire}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.load_cell(args.workload, ROOT)
    try:
        harness.look_for_chip(cell.chips)
    except harness.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        row = readings(cell, seed)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
