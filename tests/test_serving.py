"""QueryServer: batching, padding, LRU cache, async coalescing, modeled
I/O amortization, and the sharded batch axis."""
import asyncio

import numpy as np
import pytest

import repro.shardlib as sl
from repro.core import (BuildConfig, QueryEngine, build_hod,
                        dijkstra_reference, gnm_random_digraph, pack_index)
from repro.launch.serve import QueryServer

CFG = BuildConfig(max_core_nodes=32, max_core_edges=1024, seed=0)


@pytest.fixture(scope="module")
def engine():
    g = gnm_random_digraph(150, 600, seed=4)
    res = build_hod(g, CFG)
    ix = pack_index(g, res, chunk=64)
    eng = QueryEngine(ix)
    eng._graph = g  # stash for oracle checks
    return eng


def test_serve_stream_matches_engine(engine):
    server = QueryServer(engine, batch_size=8)
    sources = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], dtype=np.int32)
    results = server.serve_stream(sources)
    assert [r.source for r in results] == sources.tolist()
    direct = engine.ssd(np.unique(sources))
    by_src = {int(s): direct[i] for i, s in enumerate(np.unique(sources))}
    for r in results:
        np.testing.assert_array_equal(r.dist, by_src[r.source])
    assert server.stats.requests == 10


def test_padding_keeps_one_compiled_shape(engine):
    server = QueryServer(engine, batch_size=16)
    server.serve_stream(np.array([1, 2, 3], dtype=np.int32))
    assert server.stats.batches == 1
    assert server.stats.padded_slots == 13   # 16 - 3 real sources


def test_lru_cache_hits_and_eviction(engine):
    server = QueryServer(engine, batch_size=4, cache_entries=4)
    server.serve_stream(np.array([0, 1, 2, 3], dtype=np.int32))
    assert server.stats.cache_hits == 0
    server.serve_stream(np.array([0, 1, 2, 3], dtype=np.int32))
    assert server.stats.cache_hits == 4      # all repeats served from cache
    assert server.stats.batches == 1         # no second engine call
    # 4 new sources evict the old entries (capacity 4)
    server.serve_stream(np.array([10, 11, 12, 13], dtype=np.int32))
    server.serve_stream(np.array([0], dtype=np.int32))
    assert server.stats.batches == 3         # 0 was evicted -> re-executed


def test_cache_disabled(engine):
    server = QueryServer(engine, batch_size=2, cache_entries=0)
    server.serve_stream(np.array([5, 5], dtype=np.int32))
    server.serve_stream(np.array([5, 5], dtype=np.int32))
    assert server.stats.cache_hits == 0
    assert server.stats.batches == 2


def test_modeled_io_amortizes_with_batch_size(engine):
    sources = np.arange(32, dtype=np.int32)
    per_query = {}
    for b in (1, 8):
        server = QueryServer(engine, batch_size=b, cache_entries=0)
        server.serve_stream(sources)
        io = server.modeled_io()
        per_query[b] = io.modeled_seconds() / server.stats.requests
        assert io.rand_blocks == 0           # scans only — the paper's point
    assert per_query[8] < per_query[1] / 4   # near-linear amortization


def test_sssp_mode_returns_predecessors(engine):
    server = QueryServer(engine, batch_size=4, sssp=True)
    results = server.serve_stream(np.array([0, 7], dtype=np.int32))
    dist, pred = engine.sssp(np.array([0, 7], dtype=np.int32))
    for i, r in enumerate(results):
        assert r.pred is not None
        np.testing.assert_array_equal(r.dist, dist[i])
        np.testing.assert_array_equal(r.pred, pred[i])


def test_async_submit_coalesces(engine):
    server = QueryServer(engine, batch_size=4, max_wait_ms=5.0)

    async def drive():
        tasks = [asyncio.create_task(server.submit(s))
                 for s in [1, 2, 3, 4, 5, 6]]
        await server.drain()
        return await asyncio.gather(*tasks)

    results = asyncio.run(drive())
    assert server.stats.requests == 6
    # first four coalesced into one full batch; the rest drained
    assert results[0].batched_with == 4
    direct = engine.ssd(np.array([1, 2, 3, 4, 5, 6], dtype=np.int32))
    for i, r in enumerate(results):
        np.testing.assert_array_equal(r.dist, direct[i])


def test_async_partial_flush_on_timeout(engine):
    server = QueryServer(engine, batch_size=64, max_wait_ms=1.0)

    async def drive():
        return await server.submit(9)   # alone: must not wait forever

    r = asyncio.run(drive())
    assert r.source == 9 and server.stats.batches == 1
    np.testing.assert_array_equal(
        r.dist, engine.ssd(np.array([9], dtype=np.int32))[0])


def test_async_cache_hit_resolves_immediately(engine):
    server = QueryServer(engine, batch_size=2, max_wait_ms=1.0)

    async def drive():
        a = await server.submit(11)
        b = await server.submit(11)
        return a, b

    a, b = asyncio.run(drive())
    assert not a.cached and b.cached
    np.testing.assert_array_equal(a.dist, b.dist)


def test_async_poisoned_batch_fails_all_riders(engine):
    """An out-of-range source must fail its whole batch with an exception
    instead of stranding co-rider futures forever."""
    server = QueryServer(engine, batch_size=2, max_wait_ms=1.0)

    async def drive():
        good = asyncio.create_task(server.submit(1))
        bad = asyncio.create_task(server.submit(10**9))   # >> n
        return await asyncio.gather(good, bad, return_exceptions=True)

    results = asyncio.run(asyncio.wait_for(drive(), timeout=30))
    assert all(isinstance(r, Exception) for r in results)


def test_serve_stream_io_bytes_sum_matches_device(engine):
    """Per-request io_bytes shares (with duplicates uncharged) must sum to
    exactly what the BlockDevice metered."""
    server = QueryServer(engine, batch_size=4, cache_entries=0)
    results = server.serve_stream(np.array([5, 5, 6, 7], dtype=np.int32))
    assert sum(r.io_bytes for r in results) == \
        pytest.approx(server._sweep_bytes)
    assert server.modeled_io().bytes_seq == server._sweep_bytes


def test_sharded_batch_axis_matches_unsharded(engine):
    """Under a mesh with rules binding "batch", sweeps run data-parallel
    over sources and must produce identical distances (world size 1)."""
    import jax

    sources = np.array([0, 3, 5, 7], dtype=np.int32)
    plain = engine.ssd(sources)
    mesh = sl.make_mesh((len(jax.devices()),), ("data",))
    eng2 = QueryEngine(engine.index)
    with sl.axis_rules(mesh, {"batch": "data"}):
        sharded = eng2.ssd(sources)
    np.testing.assert_array_equal(plain, sharded)


def test_compile_count_independent_of_levels():
    """Regression guard for the SweepPlan executor's O(1) trace claim:
    a use_pallas=True SSD query traces the bucketed relax once per sweep
    direction — NOT once per level — so the trace count must not change
    between graphs with different level counts, and a repeat query with
    the same batch shape must compile nothing at all."""
    from repro.core import build_hod, grid_road_graph, pack_index
    from repro.kernels.edge_relax import ops

    counts, levels = [], []
    for side in (7, 14):
        g = grid_road_graph(side, seed=0)
        res = build_hod(g, CFG)
        ix = pack_index(g, res, chunk=64)
        eng = QueryEngine(ix, use_pallas=True)
        ops.relax_bucketed.clear_cache()   # isolate this engine's traces
        before = ops.TRACE_COUNT
        eng.ssd(np.arange(4, dtype=np.int32))
        counts.append(ops.TRACE_COUNT - before)
        levels.append(ix.n_levels)
        before = ops.TRACE_COUNT           # steady state: no retrace
        eng.ssd(np.arange(4, dtype=np.int32) + 1)
        assert ops.TRACE_COUNT == before
        assert eng._ssd_jit._cache_size() == 1
    assert levels[0] != levels[1], "graphs must differ in level count"
    # at most one relax trace per sweep direction (forward/backward plans
    # with identical [M_pad, K_fix] envelopes dedupe to a single trace);
    # the pre-plan executor traced once per LEVEL (~n_levels_f+n_levels_b)
    assert all(1 <= c <= 2 for c in counts), (counts, levels)
    assert all(c < lv for c, lv in zip(counts, levels))


def test_warm_start_compiles_at_construction(engine):
    server = QueryServer(engine, batch_size=4, warm_start=True)
    assert server.stats.batches == 0      # warmup stats were reset
    results = server.serve_stream(np.array([1, 2, 3, 4], dtype=np.int32))
    assert len(results) == 4 and server.stats.batches == 1
    np.testing.assert_array_equal(
        results[0].dist, engine.ssd(np.array([1], dtype=np.int32))[0])


def test_server_results_match_oracle(engine):
    g = engine._graph
    sources = np.array([2, 40, 77], dtype=np.int32)
    server = QueryServer(engine, batch_size=3)
    results = server.serve_stream(sources)
    oracle = dijkstra_reference(g, sources)
    for r, orc in zip(results, oracle):
        finite = np.isfinite(orc)
        assert np.allclose(r.dist[:g.n][finite], orc[finite], rtol=1e-5)


def test_knn_mode_serves_nodes_and_distances(engine):
    """--mode knn answers carry [k] node ids + distances that match the
    engine's knn rows exactly, through both the execute path and the
    LRU row cache (QueryResult.nodes must survive the round trip)."""
    k = 5
    server = QueryServer(engine, batch_size=4, mode="knn", knn_k=k,
                         cache_entries=8)
    sources = np.array([3, 1, 4, 1], dtype=np.int32)
    want_nodes, want_dist = engine.knn(np.unique(sources), k)
    by_src = {int(s): (want_nodes[i], want_dist[i])
              for i, s in enumerate(np.unique(sources))}
    for results in (server.serve_stream(sources),
                    server.serve_stream(sources)):   # 2nd pass: LRU hits
        for r in results:
            wn, wd = by_src[r.source]
            assert r.pred is None
            assert r.nodes.shape == r.dist.shape == (k,)
            np.testing.assert_array_equal(r.nodes, wn)
            np.testing.assert_array_equal(r.dist, wd)
    assert server.stats.cache_hits == 4
    assert server.stats.batches == 1     # repeats never re-executed
