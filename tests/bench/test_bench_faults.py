"""A run with the timed path broken underneath reads ``correct`` false.

Each case drives the whole run except the look for a chip, at a small
scale on the CPU, with one fault planted in the served program: an answer
that is the previous batch's (state left unchanged), half of the batch
left out, an answer altered where it is produced, two batch slots
swapped, and, on four virtual devices, the halo exchange between chips
left out. The same run without a fault reads correct, on a GAT cell held
in memory as on the cells of ``BENCHMARK.json``."""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from bench import harness, spec

SEED = 2**31 + 3


def _wrap(b, fn):
    sess = b.server.session
    inner = sess.execute_many
    sess.execute_many = lambda feats, **kw: fn(inner, feats, **kw)


def stale(b):
    last = []

    def f(inner, feats, **kw):
        out = list(inner(feats, **kw))
        prev, last[:] = list(last), out
        return prev[:len(out)] if len(prev) >= len(out) else out
    _wrap(b, f)


def half(b):
    def f(inner, feats, **kw):
        keep = max(1, len(feats) // 2)
        out = list(inner(feats[:keep], **kw))
        return [out[i % keep] for i in range(len(feats))]
    _wrap(b, f)


def altered(b):
    def f(inner, feats, **kw):
        out = [np.array(o) for o in inner(feats, **kw)]
        out[-1] = out[-1] * np.float32(1.1)
        return out
    _wrap(b, f)


def swapped(b):
    def f(inner, feats, **kw):
        return list(inner(feats, **kw))[::-1]
    _wrap(b, f)


def _run(cell, scale, breaker):
    return harness.run_cell(spec.load_cell(cell), SEED, 1.5, False,
                            devices=jax.devices(),
                            t_start=time.perf_counter(), scale=scale,
                            breaker=breaker, compile_cache=False)


@pytest.mark.parametrize("cell, scale, fault", [
    ("yelp-sage.closed16", 0.1, None),
    ("yelp-sage.closed16", 0.1, stale),
    ("yelp-sage.closed16", 0.1, half),
    ("yelp-sage.closed16", 0.1, altered),
    ("yelp-sage.closed16", 0.1, swapped),
    ("siot-gcn.poisson", 0.05, None),
    ("siot-gcn.poisson", 0.05, stale),
    ("siot-gcn.poisson", 0.05, altered),
    ("siot-gcn.closed16", 0.05, None),
    ("siot-gcn.closed16", 0.05, stale),
    ("siot-gcn.closed16", 0.05, half),
    ("siot-gcn.closed16", 0.05, altered),
])
def test_a_broken_program_reads_incorrect(cell, scale, fault):
    r = _run(cell, scale, fault)
    assert r["attempted"] > 0
    assert r["checks"]["unanswered"]["value"] == 0
    if fault is None:
        assert r["correct"] and r["failed"] == 0, r["checks"]
    else:
        assert not r["correct"] and r["failed"] > 0, r["checks"]
        assert r["checks"]["rms_err"]["value"] > r["checks"]["rms_err"]["limit"]


@pytest.mark.parametrize("fault", [None, altered])
def test_a_gat_cell_reads_correct_until_broken(fault):
    """A GAT cell held in memory, as a later configuration would add one:
    SIoT's configuration with a two-layer, one-head GAT, which the
    program serves on its segment-sum path."""
    root = spec.ROOT
    cfg = json.loads((root / "bench/configs/siot-gcn.json").read_text())
    config = dict(cfg, name="siot-gat",
                  model={"kind": "gat", "widths": [52, 16, 2]})
    cell = spec.Cell("siot-gat.closed16", 1, config, dict(
        json.loads((root / "bench/traffic/closed16.json").read_text()),
        name="closed16"), [], [])
    r = harness.run_cell(cell, SEED, 1.5, False, devices=jax.devices(),
                         t_start=time.perf_counter(), scale=0.05,
                         breaker=fault, compile_cache=False)
    assert r["attempted"] > 0
    assert r["checks"]["unanswered"]["value"] == 0
    if fault is None:
        assert r["correct"] and r["failed"] == 0, r["checks"]
        assert r["checks"]["rms_err"]["value"] < 1e-6, r["checks"]
    else:
        assert not r["correct"] and r["failed"] > 0, r["checks"]


@pytest.mark.parametrize("kernels", [False, True])
def test_halo_exchange_left_out_reads_incorrect(kernels):
    """On four virtual devices. With ``kernels``, the cell runs as on the
    chip: the Pallas path (interpret mode), whose halo crosses as uint8
    codes, checked against the reference with the wire that the TPU's
    stated precision models."""
    code = textwrap.dedent(f"""
        import json, sys, time
        sys.path[:0] = [{spec.ROOT.as_posix()!r}]
        import jax, jax.numpy as jnp
        from bench import harness, spec
        from repro.runtime import bsp

        root = spec.ROOT
        config = dict(json.loads((root / 'bench/configs/siot-gcn-4fog.json')
                                 .read_text()), name='siot-gcn-4fog')
        if {kernels}:
            config['engine'] = dict(config['engine'], aggregation='pallas')
            rounding = config['precision']['rounding']
            config['precision'] = dict(config['precision'], rounding=dict(
                rounding, cpu=[{{'halo': r['halo']}}
                               for r in rounding['tpu']][:1]))
        cell = spec.Cell(
            'siot-gcn-4fog.closed16', 4, config,
            dict(json.loads((root / 'bench/traffic/closed16.json')
                            .read_text()), name='closed16'), [], [])

        def run(breaker=None):
            return harness.run_cell(
                cell, {SEED}, 1.5,
                False, devices=jax.devices(), t_start=time.perf_counter(),
                scale=0.1, breaker=breaker, compile_cache=False)

        sound = run()
        def no_exchange(b):
            bsp._PROGRAM_CACHE.clear()
            bsp._gathered_stack = lambda a: jnp.zeros(
                a.shape[1:2] + (a.shape[0] * a.shape[2],) + a.shape[3:],
                a.dtype)
        broken = run(no_exchange)
        print(json.dumps([sound['correct'], broken['correct'],
                          broken['checks']['rms_err']['value']]))
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(spec.ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    sound, broken, err = __import__("json").loads(
        proc.stdout.strip().splitlines()[-1])
    assert sound is True and broken is False, err
