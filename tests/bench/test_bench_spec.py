"""Loading cells by name, the peaks table, and adding a configuration,
a traffic mix and a metric as new files without editing any."""
import json
import pathlib
import shutil

import pytest

from bench import spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark(ROOT)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(cell):
    c = spec.load_cell(cell, ROOT)
    assert c.config["name"] and c.traffic["loop"] in ("open", "closed")
    assert c.config["model"]["widths"][0] in (52, 100)
    assert "setup_s" in [m["name"] for m in c.end_to_end]
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"], ROOT))


def test_cells_report_their_own_metrics():
    open_ = spec.load_cell("siot-gcn.poisson", ROOT)
    assert {m["name"] for m in open_.end_to_end} == {
        "p50_ms", "p90_ms", "setup_s"}
    assert all(m["name"].endswith(".open") for m in open_.per_layer)
    closed = spec.load_cell("yelp-sage.closed16", ROOT)
    assert {m["name"] for m in closed.end_to_end} == {
        "throughput_rps", "setup_s"}
    assert all(m["name"].endswith(".closed") for m in closed.per_layer)
    assert closed.chips == 1


def test_peaks_known_and_unknown_kind():
    p = spec.peaks("TPU v5 lite", ROOT)
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99", ROOT)


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("nope.poisson", ROOT)


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    """A throwaway configuration, traffic mix and metric, added beside
    copies of the existing files, none of which is edited."""
    root = pathlib.Path(tmp_path)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((ROOT / "bench/configs/siot-gcn.json").read_text())
    cfg["model"]["kind"] = "sage"
    (root / "bench/configs/siot-sage.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/closed4.json").write_text(json.dumps(
        {"loop": "closed", "clients": 4,
         "uploads": {"pool": 8}}))
    (root / "bench/metrics/answers.closed.py").write_text(
        "def read(m):\n    return float(len(m.run.answers))\n")
    bench["configs"].append({"name": "siot-sage", "source": "x",
                             "file": "bench/configs/siot-sage.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "siot-sage.closed4",
                               "config": "siot-sage", "traffic": "closed4",
                               "chips": 1, "why": "x"})
    bench["end_to_end"][2]["workloads"].append("siot-sage.closed4")
    bench["per_layer"].append({"name": "answers.closed", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "Server", "moves": "throughput_rps",
                               "workloads": ["siot-sage.closed4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("siot-sage.closed4", root)
    assert cell.config["model"]["kind"] == "sage"
    assert cell.traffic == {"loop": "closed", "clients": 4, "name": "closed4",
                            "uploads": {"pool": 8}}
    assert [m["name"] for m in cell.per_layer] == ["answers.closed"]
    read = spec.metric_reader("answers.closed", root)

    class Run:
        answers = {0: 1, 1: 2}

    class M:
        run = Run

    assert read(M) == 2.0
    for p, data in before.items():
        assert p.read_bytes() == data, p
