"""Finds a cell's parts by name.

``BENCHMARK.json`` names every configuration, cell and metric. The parts
live in files of their own, so a later change adds a cell, a traffic mix,
a configuration or a per-layer metric by adding files and entries:

  * a configuration: the ``file`` its entry names (``bench/configs/``);
  * a traffic mix: ``bench/traffic/<traffic>.json``;
  * a per-layer metric: ``bench/metrics/<name>.py``, whose ``read(m)``
    returns the number, or None where the run has nothing to read;
  * a model kind: ``bench/kinds/<kind>.py``, the configuration's
    ``model.kind``. Its other model keys besides ``widths`` (``keys``,
    such as GAT's ``heads`` and ``skip``) reach the kind as keywords;
    a kind takes only the keys it knows. ``init(key, widths, **keys)``
    draws the weights. ``layer_widths(widths, **keys)`` describes each
    layer for the work counts (``bench/work.py``): ``fi`` and ``fo``,
    the input and output widths; ``sum_width``, the columns its
    neighbour-sum runs over; ``self_loops``, whether that sum also reads
    every vertex's own row (e + v edges); and ``dense_work(v, e, b)``,
    the FLOPs and bytes of the rest of the layer. ``reference_layer(p,
    x, last)`` is one layer of the plain reference over ``x``
    (``reference.Layer``), reading the layer's structure from its
    weights ``p``;
  * the chip's peaks: ``bench/peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
from typing import Callable, List, NamedTuple, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict            # the configuration file, plus its "name"
    traffic: dict           # the traffic file, plus its "name"
    end_to_end: List[dict]  # the entries that this cell reports
    per_layer: List[dict]


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; have "
                   f"{', '.join(e['name'] for e in entries)}")


def _reported(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    root = pathlib.Path(root)
    bench = load_benchmark(root)
    w = _named(bench["workloads"], name, "workload")
    c = _named(bench["configs"], w["config"], "configuration")
    config = dict(json.loads((root / c["file"]).read_text()),
                  name=c["name"])
    traffic = dict(json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        name=w["traffic"])
    e2e = [m for m in bench["end_to_end"] if _reported(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _reported(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def model_keys(model: dict) -> dict:
    """A configuration's model keys that its kind takes as keywords."""
    return {k: v for k, v in model.items() if k not in ("kind", "widths")}


@functools.lru_cache(maxsize=64)
def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: pathlib.Path = ROOT
                  ) -> Callable[[object], Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{name}.py"
    return _module(path, "bench_metric_" + name.replace(".", "_")
                   .replace("-", "_")).read


def model_kind(kind: str, root: pathlib.Path = ROOT):
    """The module ``bench/kinds/<kind>.py``; a kind without one is an
    error that names the file looked for."""
    path = pathlib.Path(root) / "bench" / "kinds" / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model kind {kind!r}: {path} does not "
                                f"exist")
    return _module(path, "bench_kind_" + kind.replace(".", "_")
                   .replace("-", "_"))


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """{"flops_per_s", "bytes_per_s", "source"} of one chip of this kind;
    a kind that the table lacks is an error, never a default."""
    table = json.loads((pathlib.Path(root) / "bench" / "peaks.json")
                       .read_text())["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {', '.join(table)}")
    return table[device_kind]
