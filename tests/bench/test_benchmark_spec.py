"""The benchmark's data: every cell resolves by name, the file keeps to
its contract, and a new configuration, mix, cell and metric are found
with no edit to the harness."""
import json
import math
import os
import re

import numpy as np
import pytest
from benchsupport import ROOT, copy_benchmark, dump, load, shrink
from yardstick import cell as cellmod
from yardstick import check, spec, traffic
from yardstick.reference import Reference

import control

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_file_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert all(cell in e2e[m["moves"]].get("workloads", CELLS)
                   for cell in m.get("workloads", CELLS))
    chips = [w["chips"] for w in BENCH["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)
    limit = 2 + 14 * 24
    assert limit * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    c = spec.resolve(cell, ROOT)
    traffic.validate(dict(c.traffic))
    e2e = [m["name"] for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert set(spec.readers(c)) == {m["name"] for m in c.per_layer}
    conf_entry = next(e for e in BENCH["configs"]
                      if e["name"] == c.config["name"])
    assert conf_entry["reduced"] == c.config["reduced"]
    assert len(conf_entry["source"]) <= 200


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.resolve("no-such-cell", ROOT)
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_metric", ROOT)
    with pytest.raises(spec.SpecError):
        spec.load_reader("../escape", ROOT)


def test_new_files_are_found_with_no_code_edit(tmp_path, monkeypatch):
    """A configuration, a traffic mix, a cell and a per-layer metric,
    each added as files and entries only; the new cell then runs
    through the harness at its CPU test size and comes out correct."""
    root = copy_benchmark(tmp_path)
    conf = load(root, "bench/configs/road-grid-g256.json")
    conf.update(name="road-small")
    conf["graph"]["side"] = 20
    dump(root, "bench/configs/road-small.json", conf)
    dump(root, "bench/traffic/p2p-uniform-open.json",
         {"loop": "open", "mode": "p2p", "rate_per_s": 4.0,
          "endpoints": "uniform"})
    with open(os.path.join(root, "bench/metrics/answers.p2p.py"), "w") as f:
        f.write("def read(r):\n    return float(r.batches)\n")
    bench = load(root, "BENCHMARK.json")
    bench["configs"].append(
        {"name": "road-small", "source": "https://example.org",
         "file": "bench/configs/road-small.json", "reduced": [],
         "why": "test"})
    bench["workloads"].append(
        {"name": "road-small.p2p-uniform-open", "config": "road-small",
         "traffic": "p2p-uniform-open", "chips": 1, "why": "test"})
    bench["end_to_end"].append(
        {"name": "p2p_p99_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock",
         "workloads": ["road-small.p2p-uniform-open"]})
    bench["per_layer"].append(
        {"name": "answers.p2p", "unit": "batches", "better": "higher",
         "source": "program_counter", "layer": "server",
         "moves": "p2p_p99_ms", "workloads": ["road-small.p2p-uniform-open"]})
    dump(root, "BENCHMARK.json", bench)

    cell = spec.resolve("road-small.p2p-uniform-open", root)
    assert cell.config["graph"]["side"] == 20
    assert cell.traffic["rate_per_s"] == 4.0
    assert {m["name"] for m in cell.end_to_end} == {"p2p_p99_ms",
                                                     "setup_s"}
    read = spec.readers(cell)["answers.p2p"]
    assert read(type("R", (), {"batches": 3})) == 3.0
    # the cells that were there keep what they had
    old = spec.resolve(CELLS[0], root)
    assert "answers.p2p" not in {m["name"] for m in old.per_layer}

    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    shrink(root)
    args = cellmod.parse(["--workload", "road-small.p2p-uniform-open",
                          "--seed", "3000000023", "--seconds", "0.5"])
    out = cellmod.run(args, 0.0, root=root, require_chip=False)
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == {"p2p_p99_ms", "setup_s"}


#: A directed, weighted preferential-attachment generator, as a later
#: configuration would bring it: each link in one direction, lengths 1-10.
WEB_GENERATOR = """\
import numpy as np
from yardstick.graphs import Arcs


def arcs(nodes, m_per_node, seed):
    rng = np.random.default_rng(seed)
    src, dst = [], []
    repeated = list(range(m_per_node))
    for v in range(m_per_node, nodes):
        for p in rng.choice(len(repeated), size=m_per_node, replace=False):
            u = repeated[p]
            if rng.random() < 0.5:
                src.append(v)
                dst.append(u)
            else:
                src.append(u)
                dst.append(v)
            repeated.append(u)
        repeated.extend([v] * m_per_node)
    src = np.asarray(src, dtype=np.int64)
    w = rng.integers(1, 11, size=src.shape[0]).astype(np.float64)
    return Arcs(nodes, src, np.asarray(dst, dtype=np.int64), w)
"""


def test_a_directed_graph_is_added_as_files(tmp_path, monkeypatch):
    """A directed graph kind, a configuration that states its arcs are
    directed, and an SSD closed-loop and a P2P open-loop cell, added as
    files and entries only: both cells run correct at their CPU test
    size with unreachable answers among those sampled, and the
    ``reversed`` control fails where the ``bfloat16`` one cannot, since
    no distance reaches 256."""
    root = copy_benchmark(tmp_path)
    with open(os.path.join(root, "bench/graphs/web_pa.py"), "w") as f:
        f.write(WEB_GENERATOR)
    conf = load(root, "bench/configs/road-grid-g256.json")
    conf.update(name="web-pa", reduced=[],
                graph={"kind": "web_pa", "nodes": 60000, "m_per_node": 4,
                       "seed": 3},
                guarantees={"answers": "exact distance along arcs",
                            "labels": "float32", "arcs": "directed"},
                cpu_test={"graph": {"nodes": 300}, "batch": 8})
    dump(root, "bench/configs/web-pa.json", conf)
    bench = load(root, "BENCHMARK.json")
    bench["configs"].append(
        {"name": "web-pa", "source": "https://example.org",
         "file": "bench/configs/web-pa.json", "reduced": [], "why": "test"})
    reports = {"web-pa.ssd-closed": {"sources_per_s", "setup_s"},
               "web-pa.ic13-open": {"p2p_p50_ms", "p2p_p90_ms", "setup_s"}}
    for name in reports:
        bench["workloads"].append(
            {"name": name, "config": "web-pa",
             "traffic": name.split(".", 1)[1], "chips": 1, "why": "test"})
    for metric in bench["end_to_end"]:
        metric.get("workloads", []).extend(
            name for name, names in reports.items()
            if metric["name"] in names)
    dump(root, "BENCHMARK.json", bench)
    shrink(root)

    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    for name, names in reports.items():
        args = cellmod.parse(["--workload", name, "--seed", "3000000031",
                              "--seconds", "0.5"])
        out = cellmod.run(args, 0.0, root=root, require_chip=False)
        assert out["correct"] is True, (name, out["compared"])
        assert out["attempted"] > 0 and set(out["metrics"]) == names

        cell = spec.resolve(name, root)
        mix = traffic.validate(dict(cell.traffic))
        served = cellmod.setup(cell, mix["mode"], trace=False)
        assert served.arcs.n == 300
        res = cellmod.drive(served, mix, 3000000037, 0.5)
        k = int(cell.config["check"][f"{mix['mode']}_answers"])
        sampled = [res.answered[i].answer
                   for i in check.sample(res.answered, k, 3000000037)]
        assert any(np.isinf(a).any() for a in sampled), name
        row = control.readings(cell, served.arcs, Reference(served.arcs),
                               res.answered, 3000000037)
        assert row["program"] == {"wrong": 0, "unanswered": 0}
        assert set(row["controls"]) == {"bfloat16", "reversed"}
        assert row["controls"]["reversed"]["wrong"] > 0, name
        assert row["controls"]["bfloat16"]["wrong"] == 0, name


def test_configuration_files_state_source_and_cuts():
    for entry in BENCH["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == entry["name"]
        assert {"source", "reduced", "assumed", "graph", "engine",
                "server", "check", "guarantees"} <= set(conf)
        for key in conf["reduced"]:
            assert key in conf and not key.endswith(("_dim", "_rank"))
        assert all(isinstance(v, (int, float)) and not math.isnan(v)
                   for v in conf["check"].values())
