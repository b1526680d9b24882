"""The directed web configuration ``web-pa-70k`` and what it adds: its
graph kind, its pinned data, its controls, the ``unreachable_share.ssd``
reader, and cells that kept their metrics when it came."""
import types

import numpy as np
import pytest
from benchsupport import ROOT
from test_benchmark_yardstick import _arcs_digest, _config
from yardstick import graphs, reference, spec
from yardstick.cell import Readings

WEB, ROAD = "web-pa-70k.ssd-closed", "road-grid-g256.ssd-closed"
SOCIAL = "social-pa-24k.ic13-open"
MS = 1_000_000   # ns


def test_full_size_web_arcs_keep_their_pinned_digest():
    """SHA-256 over ``n``, ``src``, ``dst`` and ``w``, as
    ``test_full_size_arcs_keep_their_pinned_digest`` computes it."""
    arcs = graphs.generate(_config("web-pa-70k")["graph"])
    assert (arcs.n, arcs.src.shape[0]) == (70000, 419964)
    assert _arcs_digest(arcs) == (
        "309de1e446fd3f28182a2de214ff79b6d229e37249cbc1e9de376f8c41202ff1")


@pytest.mark.parametrize("nodes, m_per_node, seed",
                         [(300, 6, 3), (157, 4, 8), (40, 2, 1)])
def test_power_law_web_repeats_the_repositorys_digraph(nodes, m_per_node,
                                                       seed):
    from repro.core import from_edges, power_law_digraph

    mine = graphs.generate({"kind": "power_law_web", "nodes": nodes,
                            "m_per_node": m_per_node, "seed": seed})
    theirs = power_law_digraph(nodes, m_per_node, seed=seed, weighted=True)
    g = from_edges(mine.n, mine.src, mine.dst, mine.w)
    for a, b in zip(g.edge_list(), theirs.edge_list()):
        assert np.array_equal(a, b)
    pairs = set(zip(mine.src.tolist(), mine.dst.tolist()))
    assert any((d, s) not in pairs for s, d in pairs)   # not symmetrized


def test_web_configuration_gets_the_bfloat16_and_reversed_controls():
    conf = _config("web-pa-70k")
    arcs = graphs.generate({**conf["graph"], **conf["cpu_test"]["graph"]})
    got = reference.controls_for(conf["guarantees"], arcs, farthest=None)
    assert set(got) == {"bfloat16", "reversed"}


def _span(name, t0, **args):
    return {"name": name, "tname": "MainThread", "t0": t0,
            "t1": t0 + MS, "args": args}


def _web_spans(mode="ssd", tagged=True):
    """A warm-up call (no batch id) and two served batches, each
    ``(batch, fill, unreachable)`` on its three phase spans."""
    out = []
    for batch, fill, unreachable in ((None, 32, 5000), (1, 32, 320),
                                     (2, 16, 40)):
        tags = dict(mode=mode, fill=fill)
        if batch is not None:
            tags["batch"] = batch
        if tagged:
            tags["unreachable"] = unreachable
        out += [_span(n, 10 * MS * len(out), **tags) for n in
                ("engine.dispatch", "engine.wait", "engine.readback")]
    return out


def _readings(spans):
    return Readings(spec.resolve(WEB, ROOT), "TPU v5 lite", None, spans,
                    batches=2, batch_size=32,
                    index=types.SimpleNamespace(n=1000))


def test_unreachable_share_reads_the_tag_over_fill_times_nodes():
    read = spec.load_reader("unreachable_share.ssd", ROOT)
    # (320 + 40) / ((32 + 16) * 1000); the warm-up call left out
    assert read(_readings(_web_spans())) == pytest.approx(0.75)
    assert read(_readings(_web_spans(mode="p2p"))) is None
    assert read(_readings(_web_spans(tagged=False))) is None


SSD_LAYERS = {"engine_device_ms.ssd", "edge_relax_roofline.ssd",
              "device_idle_share.ssd", "core_rounds.ssd",
              "core_settled_share.ssd", "readback_ms.ssd"}

#: Each cell's end-to-end and per-layer metrics: the two older cells
#: exactly as before the web cell came.
METRICS = {
    ROAD: ({"sources_per_s", "setup_s"}, SSD_LAYERS),
    SOCIAL: ({"p2p_p50_ms", "p2p_p90_ms", "setup_s"},
             {"coalesce_wait_ms.p2p", "engine_device_ms.p2p",
              "device_idle_share.p2p", "core_rounds.p2p",
              "readback_ms.p2p", "server_host_ms.p2p"}),
    WEB: ({"sources_per_s", "setup_s"},
          SSD_LAYERS | {"unreachable_share.ssd"}),
}


@pytest.mark.parametrize("cell", sorted(METRICS))
def test_each_cell_resolves_to_its_metrics(cell):
    c = spec.resolve(cell, ROOT)
    e2e, layers = METRICS[cell]
    assert {m["name"] for m in c.end_to_end} == e2e
    assert {m["name"] for m in c.per_layer} == layers
