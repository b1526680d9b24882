"""The engine's phase spans and the bellman core search's counts
(DESIGN.md §11).

Past ``pack_index``'s ``closure_limit`` the engine searches the core in
bellman mode, and its programs return the search's round count and each
row's settle round beside the answer; the SSD programs add each row's
count of unreachable nodes.  A traced public call records
``engine.dispatch``, ``engine.wait`` and ``engine.readback``, tagged
with the server's batch id and fill and with those counts; the serving
thread's phases follow one another without overlap, and reach the
profiler's own trace.
"""
import asyncio
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BuildConfig, QueryEngine, build_hod,
                        gnm_random_digraph, pack_index)
from repro.launch.serve import QueryServer
from repro.obs import MIRRORED, Tracer, validate_chrome_trace

PHASES = ("engine.dispatch", "engine.wait", "engine.readback")
SOURCES = np.array([3, 17, 40, 41, 77, 101], np.int32)
TARGETS = SOURCES[::-1].copy()


@pytest.fixture(scope="module")
def bellman_ix():
    g = gnm_random_digraph(120, 480, seed=9, weighted=True)
    res = build_hod(g, BuildConfig(max_core_nodes=24, max_core_edges=512,
                                   seed=0))
    ix = pack_index(g, res, chunk=64, closure_limit=8)
    assert ix.n_core > 8 and ix.core_closure.shape[0] == 0
    return ix


@pytest.fixture(scope="module")
def engine(bellman_ix):
    eng = QueryEngine(bellman_ix)
    assert eng.core_mode == "bellman"
    return eng


def _recount(eng, sources):
    """NumPy bellman fixpoint over the core block of the forward-swept
    state: ``(rounds, settle)`` as the program counts them."""
    ix = eng.index
    state = eng._run_plan(eng._init_state(jnp.asarray(ix.perm[sources])),
                          eng._plans.f, eng._relax_level)
    lo, c = ix.n_noncore, ix.n_core
    d = np.asarray(state)[:, lo:lo + c]
    adj = np.asarray(eng._core)
    rounds, settle, changed = 0, np.zeros(len(sources), np.int32), True
    while changed and rounds < c:
        nd = np.minimum(d, np.min(d[:, :, None] + adj[None], axis=1))
        moved = np.any(nd < d, axis=1)
        rounds += 1
        settle[moved] = rounds
        changed, d = bool(moved.any()), nd
    return rounds, settle


def test_core_counts_match_a_numpy_recount(engine):
    rounds, settle = _recount(engine, SOURCES)
    assert rounds > 1 and settle.max() == rounds - 1
    ends = jnp.asarray(engine.index.perm[SOURCES])
    _, c_ssd = engine._ssd_jit(engine._plans, engine._core, ends)
    _, c_p2p = engine._p2p_jit(
        engine._plans, engine._core, ends,
        jnp.asarray(engine.index.perm[TARGETS]))
    assert set(c_ssd) == {"core_rounds", "core_row_rounds", "core_sparse",
                          "unreachable"}
    assert set(c_p2p) == {"core_rounds", "core_row_rounds", "core_sparse"}
    for c in (c_ssd, c_p2p):
        assert int(c["core_rounds"]) == rounds
        assert int(c["core_sparse"]) == 0
        np.testing.assert_array_equal(np.asarray(c["core_row_rounds"]),
                                      settle)

    tracer, fill = Tracer(), 4
    engine.tracer = tracer
    try:
        with tracer.bind(batch=7, fill=fill):
            answer = engine.ssd(SOURCES)
    finally:
        engine.tracer = None
    spans = tracer.spans()
    assert [s["name"] for s in spans] == list(PHASES)
    for s in spans:
        assert s["args"] == {"mode": "ssd", "batch": 7, "fill": fill,
                             "core_rounds": rounds,
                             "core_row_rounds": int(settle[:fill].sum()),
                             "core_sparse": 0,
                             "unreachable": int(np.isinf(
                                 answer[:fill]).sum())}


def test_answers_are_bit_identical_with_a_tracer(bellman_ix):
    plain, traced = QueryEngine(bellman_ix), QueryEngine(bellman_ix)
    traced.tracer = Tracer()
    calls = [lambda e: e.ssd(SOURCES), lambda e: e.p2p(SOURCES, TARGETS),
             lambda e: e.sssp(SOURCES), lambda e: e.ssd_within(SOURCES, 9.0),
             lambda e: e.knn(SOURCES, 5)]
    for call in calls:
        for a, b in zip(jax.tree.leaves(call(plain)),
                        jax.tree.leaves(call(traced))):
            np.testing.assert_array_equal(a, b)
    modes = [s["args"]["mode"] for s in traced.tracer.spans()
             if s["name"] == "engine.readback"]
    assert modes == ["ssd", "p2p", "sssp", "within", "ssd"]


def test_programs_are_named_for_what_they_run(engine):
    ends = jnp.asarray(engine.index.perm[SOURCES])
    lowered = engine._ssd_jit.lower(engine._plans, engine._core, ends)
    assert lowered.as_text().startswith("module @jit__ssd_impl ")
    text = lowered.as_text(debug_info=True)
    for scope in ("forward_sweep", "core_search", "backward_sweep"):
        assert f"/{scope}/" in text
    assert "/meet/" in engine._p2p_jit.lower(
        engine._plans, engine._core, ends, ends).as_text(debug_info=True)


def test_a_patched_core_search_still_traces_and_counts_nothing(
        bellman_ix, monkeypatch):
    monkeypatch.setattr(QueryEngine, "_core_update",
                        lambda self, dist, core, mode: dist)
    eng = QueryEngine(bellman_ix)
    eng.tracer = Tracer()
    eng.p2p(SOURCES, TARGETS)
    spans = eng.tracer.spans()
    assert [s["name"] for s in spans] == list(PHASES)
    assert all("core_rounds" not in s["args"] for s in spans)


@pytest.mark.parametrize("mode", ["ssd", "p2p"])
def test_serving_thread_phases_follow_one_another(bellman_ix, mode):
    """Coalesced batches through ``submit``: each batch's phases and
    ``server.resolve`` lie on the serving thread without overlap, and
    every span of a batch carries its id."""
    tracer = Tracer()
    server = QueryServer(QueryEngine(bellman_ix), batch_size=4,
                         max_wait_ms=1.0, cache_entries=0, mode=mode,
                         tracer=tracer)

    async def drive():
        tasks = [asyncio.create_task(
            server.submit(int(s), int(t) if mode == "p2p" else None))
            for s, t in zip(SOURCES, TARGETS)]
        await server.drain()
        return await asyncio.gather(*tasks)

    assert len(asyncio.run(drive())) == len(SOURCES)
    spans = tracer.spans()
    serving = sorted((s for s in spans if s["name"] in MIRRORED),
                     key=lambda s: s["t0"])
    assert {s["tname"] for s in serving} == {"MainThread"}
    for a, b in zip(serving, serving[1:]):
        assert a["t1"] <= b["t0"], (a, b)
    batches = {s["args"]["batch"] for s in spans
               if s["name"] == f"query.{mode}"}
    assert batches == {1, 2}
    for name in PHASES + ("server.resolve", "coalesce.wait"):
        assert {s["args"]["batch"] for s in spans
                if s["name"] == name} == batches, name
    fills = {s["args"]["batch"]: s["args"]["fill"] for s in spans
             if s["name"] == "engine.readback"}
    assert fills == {1: 4, 2: 2}
    assert all(s["args"]["core_rounds"] > 0 for s in spans
               if s["name"] in PHASES)
    assert validate_chrome_trace(tracer.chrome()) == []


def test_phase_spans_reach_the_profilers_trace(engine, tmp_path):
    from jax.profiler import ProfileData

    engine.ssd(SOURCES)                   # compiled outside the trace
    engine.tracer = Tracer()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            engine.ssd(SOURCES)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.tracer = None
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    host = [str(e.name) for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    for name in PHASES:
        assert host.count(name) == 1, name
