"""The program's own spans in a trace: keyed by path, summed, read as
per-layer numbers, and given the device's idle time they cover."""
import time

import jax
import pytest

from bench import harness, program_spans, spec, trace
from bench.trace import Event
from repro.runtime import tracing

SEED = 2**31 + 5


def _ev(name, s, e, **stats):
    return (name, s, e, stats)


def test_paths_nest_by_thread_and_time():
    spans = program_spans.nest([
        _ev("server.batch", 1.0, 9.0), _ev("server.drain", 0.0, 10.0),
        _ev("collect", 2.0, 4.0, rows=5), _ev("daq.lossless", 2.5, 3.5),
        _ev("server.price", 5.0, 8.0), _ev("daq.lossless", 6.0, 7.0),
        _ev("collect", 10.0, 11.0, rows=7)])
    assert [s.path for s in spans] == [
        "server.drain", "server.drain/server.batch",
        "server.drain/server.batch/collect",
        "server.drain/server.batch/collect/daq.lossless",
        "server.drain/server.batch/server.price",
        "server.drain/server.batch/server.price/daq.lossless", "collect"]
    tot = program_spans.totals(spans + [spans[2]])
    assert tot["server.drain/server.batch/collect"] == (4.0, 2, {"rows": 10})
    assert tot["collect"] == (1.0, 1, {"rows": 7})
    # Per request: every path that ends in ``collect`` is one request.
    assert program_spans.per(tot, ["collect/daq.lossless"], "collect") == (
        pytest.approx(1.0 / 3))
    assert program_spans.per(tot, ["server.price"], "server.batch") == 3.0
    assert program_spans.per(tot, ["execute.wait"], "server.batch") is None


def test_idle_goes_to_the_innermost_span_by_path():
    spans = program_spans.nest([
        _ev("server.drain", 1.0, 3.0), _ev("server.batch", 1.0, 3.0),
        _ev("collect", 1.2, 2.0), _ev("daq.lossless", 1.4, 1.8)])
    # The benchmark's own spans: one over the whole drain, and one that
    # covers the program's collect exactly.
    bench_spans = [Event("drain", 0.9, 3.1), Event("collect", 1.2, 2.0)]
    devices = {0: [Event("op", 2.0, 2.5)]}
    r = program_spans.reduce(devices, bench_spans, spans, 4.0)
    assert r.idle_by_span == pytest.approx({
        "harness": 0.9 + 0.9, "drain": 0.1 + 0.1,
        "server.drain/server.batch": 0.2 + 0.5,
        "server.drain/server.batch/collect": 0.2 + 0.2,
        "server.drain/server.batch/collect/daq.lossless": 0.4})
    assert sum(r.idle_by_span.values()) == pytest.approx(4.0 - 0.5)


@pytest.mark.parametrize("cell, scale", [("siot-gcn.poisson", 0.05),
                                         ("yelp-sage.closed16", 0.05)])
def test_served_cell_traces_every_span(tmp_path, cell, scale):
    """A traced run of the cell at a small scale on the CPU: every span
    the program declares is there, once per batch or per request, the
    codec's quantize sits under both the collect and the pricing and its
    lossless stage under the pricing alone, and the upload carries at
    least every request's feature table."""
    c = spec.load_cell(cell)
    r = harness.run_cell(c, SEED, 1.5, True, devices=jax.devices(),
                         t_start=time.perf_counter(), scale=scale,
                         trace_dir=str(tmp_path), compile_cache=False)
    assert r["correct"]
    path = trace.find_xplane(str(tmp_path))
    spans = program_spans.read_spans(path)
    tot = program_spans.totals(spans)
    assert {p.rsplit("/", 1)[-1] for p in tot} == set(tracing.SPANS)

    _, bench_spans, window = trace.read_events(path)
    drains = sum(s.name == "drain" for s in bench_spans)
    requests = r["attempted"]
    top = "server.drain/server.batch"
    assert tot["server.drain"].count == tot[top].count == drains
    assert tot[top].stats["size"] == requests
    assert tot[top + "/collect"].count == requests
    assert tot[top + "/execute"].stats["batch_size"] == requests
    for stage in ("execute.dispatch", "execute.wait", "execute.download"):
        assert tot[f"{top}/execute/{stage}"].count >= drains
    for parent in ("collect", "server.price"):
        assert f"{top}/{parent}/daq.quantize" in tot
    assert f"{top}/server.price/daq.lossless" in tot
    assert f"{top}/collect/daq.lossless" not in tot
    assert tot[top + "/collect/daq.dequantize"].count == requests

    # Inside and outside views of the same calls agree.
    bench_collect = sum(s.end - s.start for s in bench_spans
                        if s.name == "collect")
    assert 0.9 * bench_collect <= tot[top + "/collect"].seconds \
        <= bench_collect

    v = tot[top + "/collect"].stats["rows"] // requests
    f = c.config["model"]["widths"][0]
    upload = tot[top + "/execute/execute.dispatch"].stats["upload_bytes"]
    assert upload >= requests * v * f * 4

    nums = program_spans.per_layer(tot)
    assert nums.pop("lossless_ms") is None   # no lossless stage in collect
    assert all(x is not None and x > 0 for x in nums.values()), nums
    assert nums["upload_mb"] * drains * 1e6 == pytest.approx(upload)

    # The CPU trace has no TPU plane: stand the waits in for device work,
    # and every idle second still goes to exactly one span or the loop.
    waits = [Event("op", s.start, s.end) for s in spans
             if s.path.endswith("execute.wait")]
    red = program_spans.reduce({0: waits}, bench_spans, spans, window)
    assert sum(red.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s[0])
    assert top + "/server.price/daq.lossless" in red.idle_by_span


def test_recorded_chip_trace_with_program_spans():
    """A trace recorded on one TPU v5e chip: 4.4 s of ``siot-gcn`` under
    open-loop load (``run_cell`` with a ``trace_dir``), with the program's
    spans. Hand-checked: 14 batches of 19 requests; every batch uploads
    the edge arrays (292,234 directed edges, 12 bytes each) and 16,216 x
    52 float32 features per request; the codec's lossless stage holds
    the most idle time of any program span, under collect and under
    pricing alike."""
    path = str(spec.ROOT / "tests/bench/traces/"
                           "siot-gcn.poisson.program.xplane.pb.gz")
    red, tot = program_spans.load(path)
    top = "server.drain/server.batch"
    assert tot["server.drain"].count == tot[top].count == 14
    assert tot[top].stats["size"] == tot[top + "/collect"].count == 19
    assert tot[top + "/collect"].stats["rows"] == 19 * 16216
    assert tot[top + "/execute/execute.dispatch"].stats["upload_bytes"] == (
        14 * 292234 * 12 + 19 * 16216 * 52 * 4)
    assert tot[top + "/execute/execute.download"].stats[
        "download_bytes"] == 19 * 16216 * 2 * 4
    assert tot[top + "/collect"].seconds == pytest.approx(1.477910846)
    assert tot[top + "/server.price"].seconds == pytest.approx(1.13736953)
    assert red.window_s == pytest.approx(4.416436705)
    assert red.busy_s == pytest.approx([0.111927656])
    assert sum(red.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s[0])
    idle = sorted(red.idle_by_span.items(), key=lambda kv: -kv[1])
    assert [n for n, _ in idle[:3]] == [
        "harness", top + "/collect/daq.lossless",
        top + "/server.price/daq.lossless"]
    assert idle[1][1] == pytest.approx(1.106690841)
    # The benchmark's own spans keep only the slivers of their wrappers.
    assert red.idle_by_span["drain"] < 0.001
    nums = program_spans.per_layer(tot)
    assert nums["price_ms"] == pytest.approx(81.2406807)
    assert nums["lossless_ms"] == pytest.approx(58.2468864)
    assert nums["upload_mb"] == pytest.approx(8.084353143)
