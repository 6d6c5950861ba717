"""One run of one cell: set up, warm up, measure, check, report.

``run_cell`` is the whole run without the look for a chip, so tests drive
it on the CPU at a small scale; ``bench/run.py`` looks for the chip first.

Set-up builds the cell's graph from its configuration, makes the weights on
the device in one jitted call from the seed, compiles the plan with
``Engine.compile``, opens ``plan.server(max_batch=...)``, draws the upload
pool from the seed and serves every batch size the cell's traffic can
form once. The window then drives ``Server.submit`` / ``Server.drain`` on
the host's clock (``bench/traffic.py``). Afterwards every answer is checked
against the plain reference (``bench/reference.py``) at the precision the
configuration states.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import List, Optional

import numpy as np

from bench import graphs, reference, spans as spans_mod, spec, stats, trace
from bench import traffic, work


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def look_for_chip(chips: int):
    """The devices of a TPU host with at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX found "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs


def use_compile_cache(root) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every
    program however fast it compiled."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.pathlib.Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def make_params(kind: str, widths: List[int], seed: int, **keys):
    """The kind's weights (``init`` of ``bench/kinds/<kind>.py``, given the
    configuration's other model ``keys``), made on the device in one
    jitted call from the seed."""
    import jax

    model = spec.model_kind(kind)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return jax.jit(lambda k: model.init(k, widths, **keys))(key)


class CompileCounter:
    """Counts jit traces and backend compiles while ``on``; one listener
    per process."""
    _instance = None

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._event)
        cls._instance.traces = cls._instance.compiles = 0
        return cls._instance

    def __init__(self):
        self.on = False
        self.traces = 0
        self.compiles = 0

    def _event(self, name, secs, **kw):
        if not self.on:
            return
        if name.endswith("jaxpr_trace_duration"):
            self.traces += 1
        elif name.endswith("backend_compile_duration"):
            self.compiles += 1


def build(cell: spec.Cell, seed: int, scale: Optional[float] = None):
    """The cell's server and everything the check needs, from the seed."""
    from repro.api import Engine
    from repro.gnn.graph import from_edge_list

    cfg = cell.config
    model, eng = cfg["model"], cfg["engine"]
    raw = graphs.make(cfg["dataset"], cfg["scale"] if scale is None
                      else scale, cfg["graph_seed"])
    widths = list(model["widths"])
    keys = spec.model_keys(model)
    if widths[0] != raw.features.shape[1]:
        raise ValueError(f"{cfg['name']}: input width {widths[0]} != "
                         f"feature width {raw.features.shape[1]}")
    graph = from_edge_list(raw.num_vertices, raw.edges, raw.features)
    params = make_params(model["kind"], widths, seed, **keys)
    plan = Engine((params, model["kind"]), cluster=eng["cluster"],
                  executor=eng["executor"], aggregation=eng["aggregation"],
                  compressor=eng["compressor"],
                  exchange=eng["exchange"]).compile(graph)
    server = plan.server(max_batch=int(cfg["max_batch"]))
    pool = traffic.upload_pool(raw.features,
                               int(cell.traffic["uploads"]["pool"]),
                               cfg["uploads"], seed)
    edges = reference.directed_edges(raw.num_vertices, raw.edges)
    # The vertex -> fog map in the raw graph's vertex order, which the
    # check still needs once the plan is freed.
    assign = np.array(plan.placement.assignment, copy=True)
    halo = (work.halo_pairs(edges.senders, edges.receivers, assign)
            if plan.num_fogs > 1 and eng["executor"] == "mesh-bsp" else 0)
    return SimpleNamespace(
        kind=model["kind"], widths=widths, model_keys=keys, params=params,
        server=server, plan=plan, pool=pool, edges=edges, assign=assign,
        halo=halo, v=raw.num_vertices, e=len(edges.senders),
        max_batch=int(cfg["max_batch"]))


def serve_fn(b):
    """``serve(ids)``: submit the requests' uploads, drain, and return
    their answers in order; a drain that forms more than one batch, or
    that answers other requests, is an error of the loop."""
    server, pool = b.server, b.pool

    def serve(ids):
        for k in ids:
            server.submit(pool[k % len(pool)])
        out = sorted(server.drain(), key=lambda r: r.request_id)
        if len(out) != len(ids) or len({r.batch_index for r in out}) != 1:
            raise RuntimeError(f"a drain of {len(ids)} requests formed "
                               f"{len({r.batch_index for r in out})} batches "
                               f"with {len(out)} answers")
        b.sizes.append(out[0].batch_size)
        return [r.embeddings for r in out]

    return serve


def batch_sizes(cell: spec.Cell, max_batch: int) -> List[int]:
    """The batch sizes the cell's traffic can form."""
    t = cell.traffic
    if t["loop"] == "closed":
        return [min(int(t["clients"]), max_batch)]
    return list(range(1, max_batch + 1))


def wire(b, precisions) -> Optional[np.ndarray]:
    """Per directed edge, whether its sender and receiver sit on different
    fogs, where a stated precision rounds the rows that cross the wire
    between them; None where none does."""
    if all(p.halo is None for p in precisions):
        return None
    return b.assign[b.edges.senders] != b.assign[b.edges.receivers]


def check(b, run: traffic.Run, limit: float):
    """Compare every answer with the reference of its upload, computed at
    each rounding that the configuration's precision admits on this
    platform (the halo wire's included), by ``rms_err`` against the
    nearest of them.

    Returns (numbers compared with their limits, requests that failed)."""
    refs = {}
    cross = wire(b, b.precisions)
    worst, failed = 0.0, 0
    for k in range(len(run.done)):
        if k not in run.answers:
            failed += 1
            continue
        p = k % len(b.pool)
        if p not in refs:
            x = reference.daq(b.pool[p], b.edges.degree)
            refs[p] = [reference.forward(b.kind, b.params_np, b.edges, x,
                                         prec, cross)
                       for prec in b.precisions]
        err = min(reference.scaled_rms(run.answers[k], *ref)
                  for ref in refs[p])
        worst = max(worst, err)
        failed += not err <= limit
    missing = len(run.done) - len(run.answers)
    return {"rms_err": {"value": worst, "limit": limit},
            "unanswered": {"value": missing, "limit": 0}}, failed


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             devices, t_start: float, root=spec.ROOT,
             scale: Optional[float] = None,
             trace_dir: Optional[str] = None,
             breaker=None, compile_cache: bool = True) -> dict:
    """The cell's result line. ``breaker(b)`` plants a fault in the built
    program, for the tests that show a broken program reads incorrect;
    tests also leave the persistent compile cache alone."""
    import jax

    if compile_cache:
        use_compile_cache(root)
    chips = cell.chips
    kind = devices[0].device_kind
    peak = spec.peaks(kind, root) if devices[0].platform == "tpu" else None
    counter = CompileCounter.get()

    b = build(cell, seed, scale)
    b.sizes = []
    if breaker is not None:
        breaker(b)
    serve = serve_fn(b)
    for n in batch_sizes(cell, b.max_batch):
        serve(list(range(n)))
    b.sizes.clear()

    sp = None
    if traced:
        sp = spans_mod.Spans()
        sess = b.server.session
        sp.wrap(sess, "collect", "collect")
        sp.wrap(sess, "execute_many", "execute")
        inner = serve

        def serve(ids, _inner=inner):
            with sp.span("drain"):
                return _inner(ids)

    t = cell.traffic
    tdir = None
    if traced:
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
    counter.on = True
    setup_s = time.perf_counter() - t_start
    if traced:
        jax.profiler.start_trace(tdir, profiler_options=opts)
    t_open = time.perf_counter()
    if t["loop"] == "open":
        due = traffic.due_times(t, seconds)
        run = traffic.run_open(due, b.max_batch, serve)
    else:
        run = traffic.run_closed(int(t["clients"]), b.max_batch, seconds,
                                 serve)
    if traced:
        jax.profiler.stop_trace()
    t_close = time.perf_counter()
    counter.on = False
    log(f"window: {t_close - t_open:.3f} s, {len(run.batches)} batches, "
        f"{len(run.answers)} answers; jit traces {counter.traces}, "
        f"backend compiles {counter.compiles} inside it")

    e2e = {}
    if t["loop"] == "open":
        lat_ms = (run.done - run.due) * 1e3
        n = len(lat_ms)
        late_ms = np.asarray(run.lateness) * 1e3
        print(f"generator lateness: {len(late_ms)} wake-ups, median "
              f"{float(np.median(late_ms)) if len(late_ms) else 0.0!r} ms, "
              f"max {float(np.max(late_ms, initial=0.0))!r} ms; latency "
              f"samples {n}, beyond p90 {stats.beyond(n, 90)}", flush=True)
        e2e["p50_ms"] = stats.percentile(lat_ms, 50)
        e2e["p90_ms"] = stats.percentile(lat_ms, 90)
    else:
        rate, count, span = stats.whole_batch_rate(run.batches, seconds)
        print(f"closed loop: {count} requests in whole batches over "
              f"{span!r} s", flush=True)
        e2e["throughput_rps"] = rate
    e2e["setup_s"] = setup_s

    red = None
    if traced:
        red = trace.load(tdir, chips)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)

    mem = memory_peak(devices[:chips])
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices), "memory_peak_bytes": mem}
    m = SimpleNamespace(b=b, run=run, spans=sp, trace=red, peak=peak,
                        chips=chips)
    out_metrics = {}
    if traced:
        dev["busy_s"] = float(np.mean(red.busy_s)) if red.busy_s else 0.0
        dev["window_s"] = red.window_s
        for entry in cell.per_layer:
            value = spec.metric_reader(entry["name"], root)(m)
            if value is not None:
                out_metrics[entry["name"]] = {"value": float(value),
                                              "unit": entry["unit"]}
    else:
        for entry in cell.end_to_end:
            out_metrics[entry["name"]] = {"value": float(e2e[entry["name"]]),
                                          "unit": entry["unit"]}

    # The check runs on the host after the window, once the device peak
    # has been read and the program's state is freed.
    b.params_np = [{k: np.asarray(v) for k, v in p.items()}
                   for p in b.params]
    b.server = b.plan = b.params = None
    gc.collect()
    t_check = time.perf_counter()
    b.precisions = reference.stated(cell.config, devices[0].platform)
    checks, failed = check(b, run, float(cell.config["check"]["rms_err"]))
    correct = failed == 0
    log(f"check: {time.perf_counter() - t_check:.3f} s over "
        f"{len(run.answers)} answers")
    result = {"correct": correct, "attempted": len(run.done),
              "failed": failed, "metrics": out_metrics, "device": dev}
    if traced:
        result["breakdown"] = trace.breakdown(red)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    return result
