"""SSD served on a directed, weighted web graph, against the oracle.

``QueryServer`` in SSD mode over a bellman-mode engine with the Pallas
sweeps (interpreted here), on ``power_law_digraph(weighted=True)``:
every link one arc of random direction, so distances depend on
direction and some nodes are unreachable from a source.  Every answered
row must equal the oracle's Dijkstra exactly, ``+inf`` included, and
each batch's ``unreachable`` tag must count the ``+inf`` entries of its
real rows only: a partial batch's padding rows (copies of its last
request) are left out.
"""
import asyncio

import numpy as np
import pytest
from oracle import ShortestPathOracle

from repro.core import QueryEngine, power_law_digraph
from repro.launch.serve import QueryServer, build_served_index
from repro.obs import Tracer

BATCH = 8


@pytest.fixture(scope="module")
def web():
    g = power_law_digraph(2000, 6, seed=5, weighted=True)
    ix, _ = build_served_index(g)
    assert len(ix.plan_f.dst) and len(ix.plan_b.dst)
    return g, ShortestPathOracle(g), QueryEngine(ix, core_mode="bellman",
                                                 use_pallas=True)


def _serve(engine, sources):
    tracer = Tracer()
    server = QueryServer(engine, batch_size=BATCH, max_wait_ms=1.0,
                         cache_entries=0, mode="ssd", tracer=tracer)

    async def drive():
        tasks = [asyncio.create_task(server.submit(int(s)))
                 for s in sources]
        await server.drain()
        return await asyncio.gather(*tasks)

    return asyncio.run(drive()), tracer.spans()


@pytest.mark.parametrize("requests", [2 * BATCH, BATCH + 5])
def test_served_ssd_on_a_directed_web_graph_matches_the_oracle(web,
                                                                requests):
    g, oracle, engine = web
    sources = np.random.default_rng(requests).choice(g.n, requests,
                                                      replace=False)
    answers, spans = _serve(engine, sources)
    want = [np.asarray(oracle.ssd(s)) for s in sources]
    for s, got, w in zip(sources, answers, want):
        assert got.source == s
        assert np.array_equal(got.dist.astype(np.float64), w), s
    assert any(np.isinf(w).any() for w in want)

    readback = {s["args"]["batch"]: s["args"] for s in spans
                if s["name"] == "engine.readback"}
    assert sorted(readback) == [1, 2]
    for b, tags in readback.items():
        rows = want[(b - 1) * BATCH:b * BATCH]
        assert tags["fill"] == len(rows)
        assert tags["unreachable"] == sum(int(np.isinf(w).sum())
                                          for w in rows), b
    if requests % BATCH:
        # The padding repeats the last row: counted, the tag would differ.
        assert np.isinf(want[-1]).any()



def test_ssd_answers_come_back_row_major_in_node_order(web):
    """The SSD program gathers its state into original node order on the
    device: its answer is ``[S, n]``, equal to the state indexed by
    ``perm``, and the host's copy is C-contiguous, so a row handed to a
    caller is one contiguous block."""
    g, oracle, engine = web
    sources = np.arange(BATCH, dtype=np.int32) * 7
    ends = engine._ends(sources)
    state, _ = engine._ssd_state(engine._plans, engine._core, ends,
                                 engine.core_mode)
    answer, _ = engine._ssd_jit(engine._plans, engine._core, ends)
    assert answer.shape == (BATCH, g.n)
    got = engine.ssd(sources)
    assert got.flags.c_contiguous
    assert np.array_equal(got, np.asarray(answer))
    assert np.array_equal(got, np.asarray(state)[:, engine.index.perm])
    assert np.array_equal(got[3].astype(np.float64),
                          np.asarray(oracle.ssd(int(sources[3]))))
