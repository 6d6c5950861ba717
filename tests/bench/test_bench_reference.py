"""The reference against the program's own forward and collect at a small
scale on the CPU, its rounding, and the bfloat16 control against the
limits."""
import json

import numpy as np
import pytest

from bench import graphs, harness, reference, spec, traffic


def _setup(dataset, kind, scale, seed):
    from repro.gnn.graph import from_edge_list

    raw = graphs.make(dataset, scale, 0)
    f = raw.features.shape[1]
    params = harness.make_params(kind, [f, 16, 3], seed)
    params_np = [{k: np.asarray(v) for k, v in p.items()} for p in params]
    g = from_edge_list(raw.num_vertices, raw.edges, raw.features)
    return raw, g, params, params_np


@pytest.mark.parametrize("dataset, kind", [("siot", "gcn"), ("yelp", "sage"),
                                           ("siot", "gat")])
def test_reference_matches_gnn_apply(dataset, kind):
    from repro.gnn import models
    from repro.gnn.layers import EdgeList

    raw, g, params, params_np = _setup(dataset, kind, 0.05, seed=5)
    e = reference.directed_edges(raw.num_vertices, raw.edges)
    np.testing.assert_array_equal(np.sort(e.senders * g.num_vertices
                                          + e.receivers),
                                  np.sort(g.senders.astype(np.int64)
                                          * g.num_vertices + g.receivers))
    x = raw.features + np.float32(0.1) * np.random.default_rng(1) \
        .standard_normal(raw.features.shape, dtype=np.float32)
    want = np.asarray(models.gnn_apply(params, kind, x, EdgeList.from_graph(g)))
    got, scale = reference.forward(kind, params_np, e, x)
    assert reference.scaled_rms(got, want, scale) < 1e-6
    if kind == "sage":
        assert np.allclose(np.linalg.norm(got, axis=-1), 1.0, atol=1e-5)
    else:
        assert np.all(scale == 1.0)


@pytest.mark.parametrize("dataset", ["siot", "yelp"])
def test_reference_daq_is_the_collect(dataset):
    from repro.api.registry import COMPRESSORS

    raw, g, _, _ = _setup(dataset, "gcn", 0.05, seed=1)
    e = reference.directed_edges(raw.num_vertices, raw.edges)
    x = raw.features + np.float32(0.1) * np.random.default_rng(2) \
        .standard_normal(raw.features.shape, dtype=np.float32)
    got = COMPRESSORS.resolve("daq").roundtrip(x, g.degrees)
    np.testing.assert_array_equal(reference.daq(x, e.degree), got)
    assert not np.array_equal(got, x)   # the round trip is lossy


def test_scaled_rms_reads_missing_and_nonfinite_as_infinite():
    ref = np.ones((4, 2), np.float32)
    s = np.ones(4, np.float32)
    assert reference.scaled_rms(ref, ref, s) == 0.0
    assert reference.scaled_rms(ref[:2], ref, s) == float("inf")
    bad = ref.copy()
    bad[1, 1] = np.nan
    assert reference.scaled_rms(bad, ref, s) == float("inf")
    # Every entry weighs alike: one of eight off by 1 reads sqrt(1/8). A
    # row of scale 0 is not compared.
    off = ref.copy()
    off[0, 0] = 2.0
    assert reference.scaled_rms(off, ref, s) == pytest.approx(
        np.sqrt(1 / 8))
    assert reference.scaled_rms(off, ref, np.array([0.0, 1, 1, 1])) == 0.0


def test_stated_precision_by_platform():
    cfg = json.loads((spec.ROOT / "bench/configs/siot-gcn.json").read_text())
    tpu = reference.stated(cfg, "tpu")
    assert all(p.dense == reference.BF16 and p.store is None for p in tpu)
    assert reference.stated(cfg, "cpu") == (reference.Precision(),)
    assert reference.CONTROL.store == reference.BF16


def test_stated_rounding_is_bfloat16_operands():
    """At the stated TPU precision a matmul sees its operands rounded to
    bfloat16 and sums them exactly; the control also keeps every array in
    bfloat16."""
    x = np.array([[1.0 + 2.0**-10, 1.0]], np.float32)
    w = np.array([[1.0], [1.0]], np.float32)
    exact = reference._matmul(x, w, None)
    assert exact[0, 0] == np.float32(2.0 + 2.0**-10)
    assert reference._matmul(x, w, reference.BF16)[0, 0] == 2.0
    assert reference._round(np.float32(1.0 + 2.0**-6), reference.BF16) \
        == np.float32(1.0 + 2.0**-6)
    assert reference._round(np.float32(1.0 + 2.0**-9), reference.BF16) == 1.0


CONTROL_CASES = [("siot-gcn", 0.25), ("yelp-sage", 1.0),
                 ("siot-gcn-4fog", 0.25)]


@pytest.mark.parametrize("config, scale", CONTROL_CASES)
def test_bfloat16_control_fails_the_limit(config, scale):
    """The control (the reference one precision lower, in the program's
    place) fails the configuration's limit against the nearest rounding
    of the stated TPU precision on every seed, at a size a test run
    holds; the program on the CPU reads far below it against the CPU's
    stated precision. Where the stated precision sends halo rows over a
    wire, both the references and the control carry it, over the edges
    that the configuration's placement splits between fogs."""
    from repro.api import Engine
    from repro.gnn.graph import from_edge_list

    cfg = json.loads((spec.ROOT / f"bench/configs/{config}.json")
                     .read_text())
    limit = cfg["check"]["rms_err"]
    kind, widths = cfg["model"]["kind"], cfg["model"]["widths"]
    raw = graphs.make(cfg["dataset"], scale, cfg["graph_seed"])
    e = reference.directed_edges(raw.num_vertices, raw.edges)
    g = from_edge_list(raw.num_vertices, raw.edges, raw.features)
    tpu, cpu = reference.stated(cfg, "tpu"), reference.stated(cfg, "cpu")
    for seed in (3, 2**31 + 5, 77):
        params = harness.make_params(kind, widths, seed)
        params_np = [{k: np.asarray(v) for k, v in p.items()}
                     for p in params]
        x = reference.daq(traffic.upload_pool(raw.features, 1,
                                              cfg["uploads"], seed)[0],
                          e.degree)
        wire = None
        if tpu[0].halo is not None:
            a = np.asarray(Engine((params, kind), **dict(
                cfg["engine"], executor="single")).compile(g)
                .placement.assignment)
            wire = a[e.senders] != a[e.receivers]
            assert wire.mean() > 0.1
        refs = [reference.forward(kind, params_np, e, x, p, wire)
                for p in tpu]
        ctl, _ = reference.forward(
            kind, params_np, e, x,
            reference.CONTROL._replace(halo=tpu[0].halo), wire)
        assert min(reference.scaled_rms(ctl, *r) for r in refs) > limit, seed
        sess = Engine((params, kind), compressor="none",
                      executor="single").compile(g).session()
        got = sess.execute(x)
        ref, s = reference.forward(kind, params_np, e, x, cpu[0])
        assert reference.scaled_rms(got, ref, s) < limit / 100, seed
