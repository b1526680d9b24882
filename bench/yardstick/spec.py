"""Find a cell's parts by name.

``BENCHMARK.json`` at the checkout's root lists the cells; each names a
configuration (whose entry gives its file) and a traffic mix, found at
``bench/traffic/<traffic>.json``.  A configuration's ``graph`` block
names its kind: ``bench/graphs/<kind>.py``, which defines
``arcs(**params)`` over the block's other keys.  A per-layer metric
``<name>`` is read by ``bench/metrics/<name>.py``, which defines
``read(readings)``.  So a configuration, a graph kind, a traffic mix, a
cell or a metric is added by adding files and entries: nothing here
names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Callable, Dict, List, Optional

#: ``<checkout>``: this file is ``<checkout>/bench/yardstick/spec.py``.
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """A cell, configuration, graph kind, traffic mix or metric cannot be
    resolved, or a graph kind's generator returned malformed arcs."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # this cell's end-to-end metric entries
    per_layer: List[dict]       # this cell's per-layer metric entries
    root: str


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path)}") from None


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _checked_name(name: str, what: str) -> str:
    if not _NAME.match(name):
        raise SpecError(f"bad {what} name {name!r}")
    return name


def _applies(metric: dict, cell: str) -> bool:
    """A metric without ``workloads`` applies to every cell."""
    return cell in metric.get("workloads", [cell])


def resolve(cell_name: str, root: str = ROOT) -> Cell:
    """The cell ``cell_name`` of ``<root>/BENCHMARK.json``."""
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == cell_name),
                 None)
    if entry is None:
        raise SpecError(f"no workload {cell_name!r} in BENCHMARK.json "
                        f"(have {[w['name'] for w in bench['workloads']]})")
    conf = next((c for c in bench["configs"]
                 if c["name"] == entry["config"]), None)
    if conf is None:
        raise SpecError(f"workload {cell_name!r} names unknown config "
                        f"{entry['config']!r}")
    traffic = _checked_name(entry["traffic"], "traffic")
    return Cell(
        name=cell_name, chips=int(entry["chips"]),
        config=_load_json(os.path.join(root, conf["file"])),
        traffic=_load_json(os.path.join(root, "bench", "traffic",
                                        traffic + ".json")),
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, cell_name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, cell_name)],
        root=root)


def _load_function(folder: str, name: str, what: str, function: str,
                   root: str) -> Callable:
    """``function`` of ``<root>/bench/<folder>/<name>.py``."""
    path = os.path.join(root, "bench", folder,
                        _checked_name(name, what) + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no file for {what} {name!r} "
                        f"({os.path.relpath(path, root)})")
    mod_name = f"bench_{folder}_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn = getattr(mod, function, None)
    if not callable(fn):
        raise SpecError(f"{os.path.relpath(path, root)} defines no "
                        f"{function}()")
    return fn


def load_reader(metric: str, root: str = ROOT
                ) -> Callable[[object], Optional[float]]:
    """``read`` of ``bench/metrics/<metric>.py``."""
    return _load_function("metrics", metric, "metric", "read", root)


def load_graph(kind: str, root: str = ROOT) -> Callable[..., object]:
    """``arcs`` of ``bench/graphs/<kind>.py``, the generator of a graph
    kind."""
    return _load_function("graphs", kind, "graph kind", "arcs", root)


def readers(cell: Cell) -> Dict[str, Callable]:
    return {m["name"]: load_reader(m["name"], cell.root)
            for m in cell.per_layer}
