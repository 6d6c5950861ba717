"""Command-line entry points: the chip smoke script, the benchmark runner
and the compile-cache placement they share."""
from __future__ import annotations

import os

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def repo_on_path(monkeypatch):
    monkeypatch.syspath_prepend(REPO)


def test_chip_smoke_refuses_without_tpu(repo_on_path, capsys):
    import chip_smoke

    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr().out
    assert '"ok"' not in out and out.strip() == ""


def test_chip_smoke_reference_matches_model(repo_on_path):
    """The script's numpy GCN agrees with the JAX model on CPU, so on the
    chip a mismatch means the device path, not the reference."""
    import chip_smoke
    from repro.gnn import models
    from repro.gnn.layers import EdgeList

    graph, params = chip_smoke.build(seed=3, scale=0.02)
    feats = np.asarray(graph.features, np.float32)
    want = np.asarray(models.gnn_apply(params, "gcn", feats,
                                       EdgeList.from_graph(graph)))
    got = chip_smoke.reference_gcn(params, graph, feats)
    assert chip_smoke.rel_err(got, want) < 1e-5


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    from repro.runtime import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    try:
        assert compile_cache.enable() == want
        # With the variable set, JAX reads it itself: nothing is set here.
        expect = before if env_dir else want
        assert jax.config.jax_compilation_cache_dir == expect
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_benchmark_runner_fails_on_a_failed_phase(repo_on_path, monkeypatch,
                                                  capsys):
    from benchmarks import paper_figures, run

    def good():
        return [("good/x", 1.0, "note")]

    def bad():
        raise RuntimeError("boom")

    monkeypatch.setattr(paper_figures, "ALL", [bad, good])
    monkeypatch.setattr(run, "kernel_microbench", good)
    assert run.main() == 1
    out = capsys.readouterr().out
    assert "bad/ERROR,nan,RuntimeError: boom" in out
    assert "good/x,1,note" in out and "# FAILED: bad" in out
