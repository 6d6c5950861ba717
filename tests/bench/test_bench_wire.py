"""The reference's model of the halo wire: its 8-bit round trip against
the program's own quantizer, and the answers of the four-fog mesh on the
kernel path, whose boundary rows cross between chips as uint8 codes."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import graphs, harness, reference, spec

UINT8 = np.dtype("uint8")


def test_wire_round_trip_is_the_program_s_bit_for_bit():
    import jax.numpy as jnp

    from repro.kernels.ref import dequant_ref
    from repro.runtime import bsp

    rng = np.random.default_rng(7)
    h = rng.standard_normal((64, 52)).astype(np.float32) * np.float32(3.0)
    h[:8] = np.maximum(h[:8], 0.0)      # ReLU rows, as a hidden layer's
    h[8] = np.float32(0.25)             # all equal
    h[9] = 0.0                          # all zero (padding)
    codes, scales, mins = bsp._wire_quantize(jnp.asarray(h))
    want = np.asarray(dequant_ref(codes, scales, mins))
    got = reference.wire_round_trip(h, UINT8)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    np.testing.assert_array_equal(got[8:10], h[8:10])
    assert not np.array_equal(got, h)


@pytest.mark.parametrize("kind, dataset", [("gcn", "siot"), ("sage", "yelp")])
def test_the_wire_changes_only_what_it_is_told(kind, dataset):
    """Without ``halo`` in the precision, or with no edge on the wire, the
    forward is the wire-less one; with every edge on it, it is not."""
    raw = graphs.make(dataset, 0.05, 0)
    e = reference.directed_edges(raw.num_vertices, raw.edges)
    f = raw.features.shape[1]
    params = [{k: np.asarray(v) for k, v in p.items()}
              for p in harness.make_params(kind, [f, 16, 3], 9)]
    x = reference.daq(raw.features + np.float32(0.1), e.degree)
    want, s = reference.forward(kind, params, e, x)
    every = np.ones(len(e.senders), bool)
    got, s2 = reference.forward(kind, params, e, x, reference.Precision(),
                                every)
    assert got.tobytes() == want.tobytes() and s2.tobytes() == s.tobytes()
    halo = reference.Precision(halo=UINT8)
    got, _ = reference.forward(kind, params, e, x, halo, ~every)
    np.testing.assert_array_equal(got, want)
    got, _ = reference.forward(kind, params, e, x, halo, every)
    assert reference.scaled_rms(got, want, s) > 1e-5


def test_stated_halo_precision_is_uint8_on_the_tpu_only():
    cfg = json.loads((spec.ROOT / "bench/configs/siot-gcn-4fog.json")
                     .read_text())
    assert all(p.halo == UINT8 for p in reference.stated(cfg, "tpu"))
    assert reference.stated(cfg, "cpu") == (reference.Precision(),)


def test_mesh_kernel_path_answers_carry_the_wire():
    """SIoT at 0.05 on four virtual fogs through ``mesh-bsp`` with the
    Pallas kernels in interpret mode and DAQ collect: the halo crosses as
    uint8 codes. Its answers sit on the wire-modelled reference and at
    least ten times farther from the wire-less one; on the segment-sum
    path, which sends float32 rows, they sit on the wire-less one.

    A code flips where the program's float32 sum and the reference's
    exact one straddle a rounding tie; at this size one flip moves an
    answer by 1e-5 to 3e-5. So an answer with no flip reads under 1e-6,
    and every answer reads a tenth of its wire-less reading or less."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{spec.ROOT.as_posix()!r}]
        import numpy as np
        from bench import graphs, harness, reference, traffic
        from repro.api import Engine
        from repro.gnn.graph import from_edge_list

        raw = graphs.make('siot', 0.05, 0)
        e = reference.directed_edges(raw.num_vertices, raw.edges)
        g = from_edge_list(raw.num_vertices, raw.edges, raw.features)
        halo = reference.Precision(halo=np.dtype('uint8'))
        out = {{'pallas': [], 'segment_sum': []}}
        for seed in (1, 2**31 + 3):
            params = harness.make_params('gcn', [52, 64, 2], seed)
            pn = [{{k: np.asarray(v) for k, v in p.items()}}
                  for p in params]
            pool = traffic.upload_pool(raw.features, 2,
                                       {{'rule': 'redraw', 'share': 0.1}},
                                       seed)
            xs = np.stack([reference.daq(u, e.degree) for u in pool])
            for agg in out:
                plan = Engine((params, 'gcn'), cluster='1A+2B+1C',
                              executor='mesh-bsp', aggregation=agg,
                              compressor='daq', exchange='halo').compile(g)
                a = np.asarray(plan.placement.assignment)
                wire = a[e.senders] != a[e.receivers]
                for x, y in zip(xs, plan.session().execute_many(xs)):
                    w = reference.forward('gcn', pn, e, x, halo, wire)
                    n = reference.forward('gcn', pn, e, x)
                    out[agg].append([reference.scaled_rms(y, *w),
                                     reference.scaled_rms(y, *n),
                                     float(wire.mean())])
        print(json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(spec.ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    kernel = np.array(out["pallas"])
    assert np.all(kernel[:, 2] > 0.25)            # the split crosses fogs
    assert np.min(kernel[:, 0]) < 1e-6, kernel
    assert np.all(kernel[:, 0] < 3e-5), kernel    # a flip or two at most
    assert np.all(kernel[:, 0] * 10 <= kernel[:, 1]), kernel
    plain = np.array(out["segment_sum"])
    assert np.all(plain[:, 1] < 1e-6), plain
    assert np.all(plain[:, 0] > 10 * plain[:, 1]), plain
