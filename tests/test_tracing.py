"""The program's spans: every one is declared, carries its counters into a
captured trace, nests where the work happens, and both executor layouts
emit the executor's three stages."""
import glob
import os
import pathlib
import re
import subprocess
import sys
import tempfile
import textwrap

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import Engine
from repro.gnn import datasets, models
from repro.runtime import tracing

REPO = pathlib.Path(__file__).resolve().parent.parent
CALL = re.compile(r"tracing\.span\(\s*\"([^\"]+)\"")


def _events(directory):
    """(name, start_ns, end_ns, stats) of every ``repro.`` host event."""
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name[len(tracing.PREFIX):], e.start_ns,
                         e.start_ns + e.duration_ns, dict(e.stats))
                        for e in line.events
                        if e.name.startswith(tracing.PREFIX)]
    return out


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_every_span_in_the_program_is_declared():
    used = set()
    for path in (REPO / "src" / "repro").rglob("*.py"):
        used |= set(CALL.findall(path.read_text()))
    assert used == set(tracing.SPANS)


def test_span_carries_counters_and_late_metadata(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("collect", rows=3) as sp:
            sp.set_metadata(in_bytes=4567)
    ev, = _events(str(tmp_path))
    assert ev[0] == "collect"
    assert ev[3] == {"rows": 3, "in_bytes": 4567}


@pytest.mark.parametrize("aggregation", ["segment_sum", "pallas"])
def test_query_spans_nest_where_the_work_happens(tmp_path, aggregation):
    """Session.query outside a Server: collect holds the codec's quantize
    and dequantize, execute the executor's three stages, and the upload
    counts the feature table and the edge arrays. The query's pricing
    packs the stored table once more, outside both, and only it runs the
    lossless stage: the round trip leaves it to the wire's sizing."""
    g = datasets.load("siot", scale=0.02, seed=0)
    params = models.gnn_init(jax.random.PRNGKey(0), "gcn",
                             [g.feature_dim, 8, 4])
    sess = Engine((params, "gcn"), executor="single", compressor="daq",
                  aggregation=aggregation).compile(g).session()
    sess.query()   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        emb = sess.query().embeddings
    evs = _events(str(tmp_path))
    by = {}
    for ev in evs:
        by.setdefault(ev[0], []).append(ev)
    assert set(by) == {"collect", "daq.quantize", "daq.lossless",
                       "daq.dequantize", "execute", "execute.dispatch",
                       "execute.wait", "execute.download"}
    (collect,), (execute,) = by["collect"], by["execute"]
    (lossless,), (dequantize,) = by["daq.lossless"], by["daq.dequantize"]
    assert not _within(lossless, collect) and not _within(lossless, execute)
    assert _within(dequantize, collect)
    assert len(by["daq.quantize"]) == 2
    inside = [_within(ev, collect) for ev in by["daq.quantize"]]
    assert sum(inside) == 1
    priced, = [ev for ev, i in zip(by["daq.quantize"], inside) if not i]
    assert priced[2] <= lossless[1]
    for name in ("execute.dispatch", "execute.wait", "execute.download"):
        assert len(by[name]) == 1 and _within(by[name][0], execute)
    assert collect[3] == {"rows": g.num_vertices}
    assert execute[3] == {"batch_size": 1}
    e = g.num_edges
    assert by["execute.dispatch"][0][3]["upload_bytes"] == (
        g.num_vertices * g.feature_dim * 4 + e * 12)
    assert by["execute.download"][0][3]["download_bytes"] == emb.nbytes


def test_mesh_bsp_emits_the_executor_stages():
    """The mesh layout (four fogs on four virtual devices) marks the same
    three stages, for a single query and for a stacked batch, and its
    upload counts the partition tables it copies each call."""
    code = textwrap.dedent("""
        import glob, os, tempfile
        import jax
        from jax.profiler import ProfileData
        from repro.api import Engine
        from repro.gnn import datasets, models
        g = datasets.load('siot', scale=0.03, seed=0)
        params = models.gnn_init(jax.random.PRNGKey(0), 'gcn',
                                 [g.feature_dim, 8, 4])
        plan = Engine((params, 'gcn'), cluster='1A+2B+1C', compressor='daq',
                      executor='mesh-bsp',
                      aggregation='pallas').compile(g)
        server = plan.server(max_batch=2)
        for n in (1, 2):   # compile both batch sizes outside the trace
            for _ in range(n):
                server.submit(None)
            server.drain()
        d = tempfile.mkdtemp()
        with jax.profiler.trace(d):
            for n in (1, 2):
                for _ in range(n):
                    server.submit(None)
                server.drain()
        path, = glob.glob(os.path.join(d, '**', '*.xplane.pb'),
                          recursive=True)
        evs = [(e.name, dict(e.stats))
               for p in ProfileData.from_file(path).planes
               if p.name.startswith('/host:')
               for line in p.lines for e in line.events
               if e.name.startswith('repro.')]
        names = [n for n, _ in evs]
        for n in ('execute.dispatch', 'execute.wait', 'execute.download'):
            assert names.count('repro.' + n) == 2, (n, names)
        assert names.count('repro.server.batch') == 2
        feats = g.num_vertices * g.feature_dim * 4
        up = [s['upload_bytes'] for n, s in evs
              if n == 'repro.execute.dispatch']
        assert min(up) > feats and max(up) > 2 * feats, up
        print('OK')
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_spans_leave_the_numbers_alone():
    """A query served while a trace is captured gives the same embeddings
    as one served without."""
    g = datasets.load("siot", scale=0.02, seed=0)
    params = models.gnn_init(jax.random.PRNGKey(0), "gcn",
                             [g.feature_dim, 8, 4])
    sess = Engine((params, "gcn"), executor="single",
                  compressor="daq").compile(g).session()
    plain = sess.query().embeddings
    with tempfile.TemporaryDirectory() as d, jax.profiler.trace(d):
        traced = sess.query().embeddings
    np.testing.assert_array_equal(plain, traced)
