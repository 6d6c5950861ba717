"""Model kinds are found by file: a kind without one is an error that
names it; the GCN and SAGE files give the weights, the reference and the
work counts that the harness gave before, bit for bit; and the GAT file
is the published model, with its heads and skip from the configuration."""
import math
from types import SimpleNamespace

import numpy as np
import pytest

from bench import graphs, harness, program_spans, readers, reference, spec
from bench import trace, work


def _make_params_before(kind, widths, seed):
    """``harness.make_params`` as it was written before the kinds moved
    into files of their own."""
    import jax
    import jax.numpy as jnp

    def init(key):
        out = []
        for fi, fo in zip(widths[:-1], widths[1:]):
            rows = 2 * fi if kind == "sage" else fi
            key, kw, kb = jax.random.split(key, 3)
            lim = math.sqrt(6.0 / (rows + fo))
            out.append({"w": jax.random.uniform(kw, (rows, fo), jnp.float32,
                                                -lim, lim),
                        "b": jax.random.uniform(kb, (fo,), jnp.float32,
                                                -0.1, 0.1)})
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return jax.jit(init)(key)


def _forward_before(kind, params, e, feats, prec, wire=None):
    """``reference.forward`` as it was written before the kinds moved,
    with the halo wire as it was modelled then."""
    r = reference._round
    scale = np.ones(e.num_vertices, np.float32)
    h = r(feats, prec.store)
    deg = e.degree.astype(np.float32)[:, None]
    split = wire is not None and prec.halo is not None
    for i, p in enumerate(params):
        w = np.asarray(p["w"], np.float32)
        b = np.asarray(p["b"], np.float32)
        if split:
            a = (reference._adjacency(e, ~wire)
                 @ r(h, prec.spmm).astype(np.float64))
            a = a + (reference._adjacency(e, wire) @ r(
                reference.wire_round_trip(h, prec.halo), prec.spmm)
                .astype(np.float64))
        else:
            a = e.adj @ r(h, prec.spmm).astype(np.float64)
        a = r(a.astype(np.float32), prec.store)
        if kind == "gcn":
            out = reference._matmul((a + h) / (deg + 1.0), w, prec.dense) + b
        else:
            f = h.shape[-1]
            out = (reference._matmul(a / np.maximum(deg, 1.0), w[:f],
                                     prec.dense)
                   + reference._matmul(h, w[f:], prec.dense) + b)
        if i < len(params) - 1:
            out = np.maximum(out, 0.0)
        if kind == "sage":
            norm = np.sqrt(np.sum(np.square(out.astype(np.float64)), axis=-1,
                                  keepdims=True))
            scale = norm[:, 0].astype(np.float32)
            out = (out / np.maximum(norm, 1e-12)).astype(np.float32)
        h = r(out, prec.store)
    return h, scale


def test_a_kind_without_a_file_names_the_file():
    path = spec.ROOT / "bench" / "kinds" / "no-such-kind.py"
    with pytest.raises(FileNotFoundError, match=str(path)):
        spec.model_kind("no-such-kind")
    with pytest.raises(FileNotFoundError, match="kinds/no-such-kind.py"):
        harness.make_params("no-such-kind", [4, 2], 1)
    with pytest.raises(FileNotFoundError, match="kinds/no-such-kind.py"):
        list(work.layer_widths("no-such-kind", [4, 2]))


@pytest.mark.parametrize("kind, dataset", [("gcn", "siot"), ("sage", "yelp")])
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**40 + 77])
def test_kind_files_give_what_the_harness_gave(kind, dataset, seed):
    raw = graphs.make(dataset, 0.05, 0)
    f = raw.features.shape[1]
    widths = [f, 64, 2]
    got = harness.make_params(kind, widths, seed)
    want = _make_params_before(kind, widths, seed)
    assert [sorted(p) for p in got] == [sorted(p) for p in want]
    for g, w in zip(got, want):
        for k in g:
            assert np.asarray(g[k]).tobytes() == np.asarray(w[k]).tobytes()
    params = [{k: np.asarray(v) for k, v in p.items()} for p in got]
    e = reference.directed_edges(raw.num_vertices, raw.edges)
    x = reference.daq(raw.features + np.float32(0.05), e.degree)
    wire = np.random.default_rng(seed % 1000).random(len(e.senders)) < 0.4
    for prec in (reference.Precision(), reference.CONTROL,
                 reference.Precision(dense=reference.BF16,
                                     spmm=reference.BF16)):
        for cut, halo in ((None, None), (wire, np.dtype("uint8"))):
            prec = prec._replace(halo=halo)
            h, s = reference.forward(kind, params, e, x, prec, cut)
            h0, s0 = _forward_before(kind, params, e, x, prec, cut)
            assert h.tobytes() == h0.tobytes()
            assert s.tobytes() == s0.tobytes()


def test_layer_widths_come_from_the_kind_files():
    # (input width, weight rows, output width, the sum's width, self loops)
    assert list(work.layer_widths("gcn", [52, 64, 2])) == [
        (52, 52, 64, 52, False), (64, 64, 2, 64, False)]
    for kind in ("gcn", "sage"):
        model = spec.model_kind(kind)
        assert spec.model_kind(kind) is model
        assert list(model.layer_widths([3, 4])) == list(
            work.layer_widths(kind, [3, 4]))


def _layer_widths_before(kind, widths):
    """The kinds' ``layer_widths`` before the per-layer description."""
    for fi, fo in zip(widths[:-1], widths[1:]):
        yield fi, (2 * fi if kind == "sage" else fi), fo


def _served_spmm_before(kind, widths, v, e, b, halo=0):
    flops = nbytes = 0.0
    for fi, _, _ in _layer_widths_before(kind, widths):
        f, n = work.spmm(e, v, v, fi, b, halo)
        flops += f
        nbytes += n
    return flops, nbytes


def _served_model_before(kind, widths, v, e, b, halo=0):
    flops = nbytes = 0.0
    for fi, rows, fo in _layer_widths_before(kind, widths):
        dense_flops, dense_bytes = 2.0 * v * rows * fo * b, 4.0 * (
            rows * fo + fo)
        flops += 2.0 * e * fi * b + dense_flops
        nbytes += (4.0 * b * v * (fi + fo) + 8.0 * e + b * halo * (fi + 8.0)
                   + dense_bytes)
    return flops, nbytes


def _least_before(m, count):
    b = m.b
    total = 0.0
    for batch in m.run.batches:
        n = len(batch.ids)
        for fi, rows, fo in _layer_widths_before(b.kind, b.widths):
            total += work.least_seconds(
                *count(b.kind, [fi, fo], b.v, b.e, n, b.halo), m.peak)
    return total


COUNT_CASES = [("gcn", [52, 64, 2], 16216, 292234),
               ("sage", [100, 64, 2], 10000, 31366)]


@pytest.mark.parametrize("kind, widths, v, e", COUNT_CASES)
@pytest.mark.parametrize("halo", [0, 13687])
def test_counts_are_the_parent_s_bit_for_bit(kind, widths, v, e, halo):
    """``served_spmm``, ``served_model`` and, on the recorded chip trace's
    batches, ``spmm_roofline`` and ``mfu`` equal the formulas that counted
    a layer from its (input, output) widths, to the last bit."""
    layers = work.layer_widths(kind, widths)
    for b in range(1, 9):
        assert work.served_spmm(layers, v, e, b, halo) == \
            _served_spmm_before(kind, widths, v, e, b, halo)
        assert work.served_model(layers, v, e, b, halo) == \
            _served_model_before(kind, widths, v, e, b, halo)
    path = str(spec.ROOT / "tests/bench/traces/"
                           "siot-gcn.poisson.program.xplane.pb.gz")
    red, _ = program_spans.load(path)
    sizes = [s.stats["size"] for s in program_spans.read_spans(path)
             if s.path == "server.drain/server.batch"]
    assert len(sizes) == 14 and sum(sizes) == 19
    m = SimpleNamespace(
        trace=red, peak=spec.peaks("TPU v5 lite"), chips=1,
        run=SimpleNamespace(batches=[SimpleNamespace(ids=list(range(n)))
                                     for n in sizes]),
        b=SimpleNamespace(kind=kind, widths=widths, model_keys={}, v=v, e=e,
                          halo=halo))
    spmm_s = trace.seconds_matching(red, trace.SPMM_KERNEL)
    assert spmm_s > 0
    assert readers.spmm_roofline(m) == \
        100.0 * _least_before(m, _served_spmm_before) / spmm_s
    assert readers.mfu(m) == \
        100.0 * _least_before(m, _served_model_before) / red.window_s


def test_kinds_take_only_the_model_keys_they_know():
    cfg = {"kind": "gat", "widths": [4, 8, 2], "heads": [2, 1],
           "skip": [True, False]}
    keys = spec.model_keys(cfg)
    assert keys == {"heads": [2, 1], "skip": [True, False]}
    p = harness.make_params("gat", cfg["widths"], 3, **keys)
    assert {k: v.shape for k, v in p[0].items()} == {
        "w": (4, 8), "att_src": (2, 4), "att_dst": (2, 4), "w_res": (4, 8)}
    assert {k: v.shape for k, v in p[1].items()} == {
        "w": (8, 2), "att_src": (1, 2), "att_dst": (1, 2)}
    with pytest.raises(TypeError):
        harness.make_params("gcn", [4, 2], 1, heads=[1])
    with pytest.raises(ValueError, match="heads"):
        work.layer_widths("gat", [4, 8, 2], heads=[3, 1])
    with pytest.raises(ValueError, match="heads"):
        work.layer_widths("gat", [4, 8, 2], heads=[2])


def _random_graph(n, m, seed):
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, n, size=(m, 2))
    return reference.directed_edges(n, raw)


def _gat_dense(params, e, x):
    """GAT as the paper writes it, on a dense adjacency in float64: per
    head, scores over N(v) and v, a softmax per receiver, the weighted sum
    of z; hidden heads concatenated, plus the skip, through ELU; the last
    layer's heads averaged."""
    n = e.num_vertices
    mask = np.zeros((n, n), bool)
    mask[e.receivers, e.senders] = True
    mask[np.arange(n), np.arange(n)] = True
    h = np.asarray(x, np.float64)
    for i, p in enumerate(params):
        last = i == len(params) - 1
        k, f = p["att_src"].shape
        z = (h @ p["w"].astype(np.float64)).reshape(n, k, f)
        src = np.einsum("ukf,kf->uk", z, p["att_src"].astype(np.float64))
        dst = np.einsum("vkf,kf->vk", z, p["att_dst"].astype(np.float64))
        score = dst[:, None, :] + src[None, :, :]           # [v, u, k]
        score = np.where(score > 0, score, 0.2 * score)
        score = np.where(mask[:, :, None], score, -np.inf)
        alpha = np.exp(score - score.max(axis=1, keepdims=True))
        alpha /= alpha.sum(axis=1, keepdims=True)
        o = np.einsum("vuk,ukf->vkf", alpha, z)
        out = o.mean(axis=1) if last else o.reshape(n, k * f)
        if "w_res" in p:
            out = out + h @ p["w_res"].astype(np.float64)
        h = out if last else np.where(out > 0, out, np.expm1(
            np.minimum(out, 0.0)))
    return h


def test_gat_is_the_published_multi_head_model():
    """Three layers, heads [4, 4, 2], a skip across the middle layer, the
    last layer's heads averaged: the reference against the dense formula,
    and each layer's weights and counts from the configuration's keys."""
    e = _random_graph(36, 90, 4)
    widths, heads, skip = [6, 16, 16, 3], [4, 4, 2], [False, True, False]
    params = [{k: np.asarray(v) for k, v in p.items()} for p in
              harness.make_params("gat", widths, 11, heads=heads, skip=skip)]
    assert [p["att_src"].shape for p in params] == [(4, 4), (4, 4), (2, 3)]
    assert [p["w"].shape for p in params] == [(6, 16), (16, 16), (16, 6)]
    assert ["w_res" in p for p in params] == skip
    x = np.random.default_rng(2).standard_normal((36, 6)).astype(np.float32)
    got, scale = reference.forward("gat", params, e, x)
    want = _gat_dense(params, e, x)
    assert np.all(scale == 1.0) and got.shape == (36, 3)
    assert reference.scaled_rms(got, want, scale) < 1e-6
    layers = work.layer_widths("gat", widths, heads=heads, skip=skip)
    assert [(s.fi, s.fo, s.sum_width, s.self_loops) for s in layers] == [
        (6, 16, 16, True), (16, 16, 16, True), (16, 3, 6, True)]


def test_gat_rounds_both_operands_of_its_sum():
    """Rounding the SpMM's operands to bfloat16 moves GAT's answer; the
    control, which also stores and multiplies in bfloat16, moves it
    more."""
    raw = graphs.make("siot", 0.05, 0)
    e = reference.directed_edges(raw.num_vertices, raw.edges)
    params = [{k: np.asarray(v) for k, v in p.items()} for p in
              harness.make_params("gat", [52, 16, 3], 5, heads=[2, 3],
                                  skip=[True, False])]
    x = reference.daq(raw.features + np.float32(0.05), e.degree)
    exact, s = reference.forward("gat", params, e, x)
    spmm, _ = reference.forward("gat", params, e, x,
                                reference.Precision(spmm=reference.BF16))
    ctl, _ = reference.forward("gat", params, e, x, reference.CONTROL)
    moved = reference.scaled_rms(spmm, exact, s)
    assert moved > 1e-5
    assert reference.scaled_rms(ctl, exact, s) > moved
