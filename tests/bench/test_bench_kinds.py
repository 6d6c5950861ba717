"""Model kinds are found by file: a kind without one is an error that
names it, and the GCN and SAGE files give the weights and the reference
that the harness gave before they moved there, bit for bit."""
import math

import numpy as np
import pytest

from bench import graphs, harness, reference, spec, work


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


def _forward_before(kind, params, e, feats, prec):
    """``reference.forward`` as it was written before the kinds moved."""
    r = reference._round
    scale = np.ones(e.num_vertices, np.float32)
    h = r(feats, prec.store)
    deg = e.degree.astype(np.float32)[:, None]
    for i, p in enumerate(params):
        w = np.asarray(p["w"], np.float32)
        b = np.asarray(p["b"], np.float32)
        a = r((e.adj @ r(h, prec.spmm).astype(np.float64))
              .astype(np.float32), prec.store)
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
    path = spec.ROOT / "bench" / "kinds" / "gat.py"
    with pytest.raises(FileNotFoundError, match=str(path)):
        spec.model_kind("gat")
    with pytest.raises(FileNotFoundError, match="kinds/gat.py"):
        harness.make_params("gat", [4, 2], 1)
    with pytest.raises(FileNotFoundError, match="kinds/gat.py"):
        list(work.layer_widths("gat", [4, 2]))


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
    for prec in (reference.Precision(), reference.CONTROL,
                 reference.Precision(dense=reference.BF16,
                                     spmm=reference.BF16)):
        h, s = reference.forward(kind, params, e, x, prec)
        h0, s0 = _forward_before(kind, params, e, x, prec)
        assert h.tobytes() == h0.tobytes() and s.tobytes() == s0.tobytes()


def test_layer_widths_come_from_the_kind_files():
    assert list(work.layer_widths("gcn", [52, 64, 2])) == [
        (52, 52, 64), (64, 64, 2)]
    for kind in ("gcn", "sage"):
        model = spec.model_kind(kind)
        assert spec.model_kind(kind) is model
        assert list(model.layer_widths([3, 4])) == list(
            work.layer_widths(kind, [3, 4]))
