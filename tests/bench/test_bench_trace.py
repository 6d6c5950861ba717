"""The reduction from a profiler trace to the per-layer numbers."""
import pytest

from bench import spec, trace
from bench.trace import Event


def test_union_and_gaps():
    busy = trace.union([(0.5, 1.0), (0.2, 0.6), (2.0, 3.0), (2.5, 2.7)])
    assert busy == [(0.2, 1.0), (2.0, 3.0)]
    assert trace.gaps(busy, 4.0) == [(0.0, 0.2), (1.0, 2.0), (3.0, 4.0)]


def test_idle_goes_to_the_innermost_span():
    spans = [Event("drain", 1.0, 3.0), Event("collect", 1.2, 2.0),
             Event("execute", 2.5, 3.0)]
    got = trace.attribute((0.5, 2.6), spans)
    assert got == pytest.approx({"harness": 0.5, "drain": 0.2 + 0.5,
                                 "collect": 0.8, "execute": 0.1})


def test_reduce_two_chips():
    devices = {
        0: [Event("spmm_kernel.1", 1.0, 1.5), Event("fusion.2", 1.4, 2.0),
            Event("all-gather.3", 2.0, 2.1)],
        1: [Event("spmm_kernel.1", 1.0, 1.2), Event("all-gather.3", 2.0, 2.2)],
    }
    spans = [Event("execute", 0.9, 2.3), Event("collect", 0.0, 0.9)]
    r = trace.reduce(devices, spans, 4.0)
    assert r.chips == 2 and r.window_s == 4.0
    assert r.busy_s == pytest.approx([1.1, 0.4])
    assert trace.seconds_matching(r, trace.SPMM_KERNEL) == pytest.approx(0.7)
    assert trace.seconds_matching(r, trace.COLLECTIVE) == pytest.approx(0.3)
    idle = r.idle_by_span
    # chip 0 idles 0..1 (0.9 collect, 0.1 execute), 2.1..2.3 (execute),
    # 2.3..4 (harness); chip 1 idles 0..1, 1.2..2.0, 2.2..4.
    assert idle["collect"] == pytest.approx(1.8)
    assert idle["execute"] == pytest.approx(0.1 + 0.2 + 0.1 + 0.8 + 0.1)
    assert idle["harness"] == pytest.approx(1.7 + 1.7)
    bd = trace.breakdown(r)
    assert bd["device_ops"][0][0] == "spmm_kernel.1"
    assert len(bd["idle_gaps"]) == 3


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e chip: 8.2 s of ``siot-gcn`` under
    open-loop load with the benchmark's spans (``run_cell`` with a
    ``trace_dir``). Hand-checked: 20 served batches, each two SpMM
    launches (``block_spmm`` for singletons, ``block_spmm_batched``
    otherwise) of about 2.2 ms per singleton layer."""
    path = str(spec.ROOT / "tests/bench/traces/siot-gcn.poisson.xplane.pb.gz")
    devices, spans, window = trace.read_events(path)
    assert sorted(devices) == [0] and len(devices[0]) == 404
    assert {s.name for s in spans} == {"collect", "drain", "execute"}
    assert sum(s.name == "drain" for s in spans) == 20
    r = trace.reduce(devices, spans, window)
    assert r.window_s == pytest.approx(8.219069623)
    assert r.busy_s == pytest.approx([0.140464218])
    idle = 1.0 - r.busy_s[0] / r.window_s
    assert idle == pytest.approx(0.98291, abs=1e-5)
    spmm = {n: s for n, s in r.ops_s.items() if trace.SPMM_KERNEL.search(n)}
    assert spmm == pytest.approx({
        "block_spmm.2": 0.039407579, "block_spmm.3": 0.039407811,
        "block_spmm_batched.2": 0.013795118,
        "block_spmm_batched.3": 0.013087113})
    assert trace.seconds_matching(r, trace.COLLECTIVE) == 0.0
    # Every idle second goes to exactly one host span (or the loop).
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s[0])
    assert r.idle_by_span == pytest.approx({
        "collect": 3.708167559, "harness": 2.574551069,
        "drain": 1.637112351, "execute": 0.158774426})
    assert r.busy_s[0] <= sum(r.ops_s.values())
