"""DAQ + lossless compression: Thm 2 exactness, round-trip error bounds,
and a round trip that leaves the lossless stage to wire sizing."""
import warnings
import zlib

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional dep:
# property tests skip cleanly when hypothesis is not installed

from repro.api.registry import COMPRESSORS
from repro.core import compression as comp
from repro.core import simulation
from repro.gnn import datasets
from repro.gnn.graph import degree_cdf


@given(st.integers(0, 5000), st.integers(16, 400))
@settings(max_examples=25, deadline=None)
def test_theorem2_matches_measured_bits(seed, n):
    """Thm 2's closed-form ratio == measured quantized payload bits."""
    rng = np.random.default_rng(seed)
    degrees = rng.zipf(1.5, size=n).astype(np.int64)
    feats = rng.normal(size=(n, 8))
    th = comp.quantile_thresholds(degrees)
    packed = comp.daq_pack(feats, degrees, thresholds=th, lossless=False)
    ratio = comp.theorem2_ratio(degree_cdf_of(degrees), th)
    assert packed.measured_ratio == pytest.approx(ratio, rel=1e-12)


def degree_cdf_of(degrees):
    hist = np.bincount(degrees).astype(np.float64)
    cdf = np.cumsum(hist) / hist.sum()

    def F(d):
        d = np.asarray(d, np.int64)
        return np.where(d < 0, 0.0, cdf[np.minimum(d, len(cdf) - 1)])

    return F


def test_theorem2_limits():
    """All-low-degree -> ratio 1 (q0=64); all-high -> q3/Q = 8/64."""
    lo = np.full(100, 1)
    hi = np.full(100, 1000)
    f_lo = degree_cdf_of(lo)
    f_hi = degree_cdf_of(hi)
    assert comp.theorem2_ratio(f_lo, (500, 600, 700)) == pytest.approx(1.0)
    assert comp.theorem2_ratio(f_hi, (2, 3, 4)) == pytest.approx(8 / 64)


@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_daq_roundtrip_error_bounds(seed):
    """Dequant error per element <= scale/2 = range/(2(2^b - 1))."""
    rng = np.random.default_rng(seed)
    n, f = 64, 16
    feats = rng.normal(size=(n, f)) * 10
    degrees = rng.zipf(1.5, size=n).astype(np.int64)
    packed = comp.daq_pack(feats, degrees, lossless=False)
    rec = comp.daq_unpack(packed).astype(np.float64)
    rng_row = feats.max(1) - feats.min(1)
    for b in (8, 16):
        ids = np.flatnonzero(packed.bits_per_vertex == b)
        if ids.size:
            bound = rng_row[ids] / (2 * (2 ** b - 1)) + 1e-9
            err = np.abs(rec[ids] - feats[ids]).max(axis=1)
            assert (err <= bound * 1.001).all()
    # 64-bit bin is lossless
    ids = np.flatnonzero(packed.bits_per_vertex == 64)
    if ids.size:
        assert np.abs(rec[ids] - feats[ids]).max() < 1e-6


def test_quantile_binning_assigns_all_four_levels():
    g = datasets.load("siot", scale=0.05, seed=0)
    bits = comp.assign_bits(g.degrees)
    assert set(np.unique(bits)) <= {8, 16, 32, 64}
    assert len(set(np.unique(bits))) >= 3  # heavy tail hits several bins


def test_high_degree_gets_fewer_bits():
    degrees = np.array([0, 10, 100, 1000])
    bits = comp.assign_bits(degrees, thresholds=(5, 50, 500))
    assert list(bits) == [64, 32, 16, 8]


def test_lossless_stage_helps_on_sparse_onehot():
    """SIoT-style one-hot features compress heavily after byte shuffle."""
    g = datasets.load("siot", scale=0.05, seed=0)
    sizes = comp.end_to_end_sizes(g.features.astype(np.float64), g.degrees)
    assert sizes["wire_bytes"] < 0.1 * sizes["raw_bytes"]
    assert sizes["daq_bytes"] < 0.6 * sizes["raw_bytes"]


def test_uniform8_smaller_but_lossier_than_daq():
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(128, 32))
    degrees = rng.zipf(1.5, size=128).astype(np.int64)
    daq = comp.daq_pack(feats, degrees, lossless=False)
    uni = comp.uniform_pack(feats, 8, lossless=False)
    assert uni.quant_bits <= daq.quant_bits
    err_daq = np.abs(comp.daq_unpack(daq) - feats).mean()
    err_uni = np.abs(comp.daq_unpack(uni) - feats).mean()
    assert err_daq <= err_uni + 1e-9


# --- the round trip skips the lossless stage, which only sizes the wire ---

def _sparse_onehot(rng):
    """SIoT-like uploads: four one-hot blocks of 13 categories per row."""
    x = np.zeros((600, 52), np.float32)
    for b in range(4):
        x[np.arange(600), 13 * b + rng.integers(0, 13, 600)] = 1.0
    return x


def _dense_noisy(rng):
    """Yelp-like uploads: dense features plus a little noise."""
    x = rng.normal(size=(400, 100))
    return (x + 0.1 * rng.normal(size=x.shape)).astype(np.float32)


TABLES = {"sparse_onehot": _sparse_onehot, "dense_noisy": _dense_noisy}

#: Each COMPRESSORS entry's pack as the round trip once ran it, lossless
#: stage included.
PACK_WITH_LOSSLESS = {
    "daq": lambda x, d: comp.daq_pack(x, d),
    "daq_noll": lambda x, d: comp.daq_pack(x, d, lossless=False),
    "daq_lz4": lambda x, d: comp.daq_pack(x, d, codec="lz4"),
    "uniform8": lambda x, d: comp.uniform_pack(x, 8),
}


def _table(kind):
    rng = np.random.default_rng(7)
    x = TABLES[kind](rng)
    return x, rng.zipf(1.6, size=x.shape[0]).astype(np.int64)


@pytest.mark.parametrize("kind", sorted(TABLES))
@pytest.mark.parametrize("name", sorted(PACK_WITH_LOSSLESS) + ["none"])
def test_roundtrip_is_bitwise_the_unpack_of_the_lossless_pack(name, kind):
    x, d = _table(kind)
    got = COMPRESSORS.resolve(name).roundtrip(x, d)
    if name == "none":
        want = x.astype(np.float32)
    else:
        with warnings.catch_warnings():   # lz4 may fall back to zlib
            warnings.simplefilter("ignore", RuntimeWarning)
            packed = PACK_WITH_LOSSLESS[name](x.astype(np.float64), d)
        assert packed.lossless_payload is not None or name == "daq_noll"
        want = comp.daq_unpack(packed).astype(np.float32)
    assert got.dtype == want.dtype == np.float32
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("name", sorted(PACK_WITH_LOSSLESS))
def test_roundtrip_never_runs_the_lossless_coder(monkeypatch, name):
    calls = {"lossless": 0, "shuffle": 0}

    def counted(key, fn):
        def wrapper(*a, **kw):
            calls[key] += 1
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(comp, "lossless_compress",
                        counted("lossless", comp.lossless_compress))
    monkeypatch.setattr(comp, "byte_shuffle",
                        counted("shuffle", comp.byte_shuffle))
    x, d = _table("sparse_onehot")
    COMPRESSORS.resolve(name).roundtrip(x, d)
    assert calls == {"lossless": 0, "shuffle": 0}
    comp.daq_pack(x, d)   # the sizing pack still runs both
    assert calls["lossless"] == 1 and calls["shuffle"] > 0


def _zlib_size(x, d):
    """zlib level 6 over the byte-shuffled DAQ groups, widest bits first."""
    groups = comp.daq_pack(x, d, lossless=False).groups
    payload = b"".join(comp.byte_shuffle(groups[b][1])
                       for b in sorted(groups, reverse=True))
    return len(zlib.compress(payload, 6))


@pytest.mark.parametrize("kind", sorted(TABLES))
def test_end_to_end_sizes_still_report_the_zlib_size(kind):
    x, d = _table(kind)
    x = x.astype(np.float64)
    sizes = comp.end_to_end_sizes(x, d)
    assert sizes["wire_bytes"] == _zlib_size(x, d)
    assert sizes["wire_bytes"] < sizes["daq_bytes"]


def test_partition_wire_bytes_still_price_the_zlib_size():
    g = datasets.load("siot", scale=0.05, seed=0)
    ids = np.arange(g.num_vertices)
    feats = g.features.astype(np.float64)
    overhead = g.num_vertices * simulation.PROTOCOL_BYTES_PER_VERTEX
    assert simulation._partition_wire_bytes(g, ids, "daq") == (
        overhead + _zlib_size(feats, g.degrees))
