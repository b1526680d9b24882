"""The bellman core round over the core's arcs.

Where a core is sparse enough (``sparse_core_round``), each bellman
round relaxes ``plan_core``'s arcs by gather, per-row minimum and
scatter-min instead of a min-plus with the dense ``[C, C]`` adjacency.
It is the same Jacobi round: the labels after every round, the round
count and each row's settle round equal the dense form's, and the
answers equal Dijkstra's bit for bit.  The test cores are small, so
each form is forced by setting ``SPARSE_CORE_RATIO`` here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (QueryEngine, dijkstra_reference, grid_road_graph,
                        power_law_digraph)
from repro.core import query
from repro.core.query import _block_rows, _minplus_blocks, sparse_core_round
from repro.launch.serve import build_served_index
from repro.obs import Tracer
from repro.storage import IndexStore, StreamingQueryEngine

from test_core_hoist import RESHAPES, _eqns, _subjaxprs

GRAPHS = ["grid", "digraph"]
SOURCES = np.array([0, 5, 77, 150, 333, 511, 590, 599], np.int32)
TARGETS = SOURCES[::-1].copy()


@pytest.fixture(scope="module", params=GRAPHS)
def forms(request):
    """``(graph, dense, sparse)`` engines on one index: a side-32 road
    grid, or a directed, weighted preferential-attachment graph."""
    if request.param == "grid":
        g = grid_road_graph(32, seed=1)
    else:
        g = power_law_digraph(600, 3, seed=0, weighted=True)
    ix, _ = build_served_index(g)
    dense = QueryEngine(ix, core_mode="bellman")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(query, "SPARSE_CORE_RATIO", 0)
        sparse = QueryEngine(ix, core_mode="bellman")
    assert not dense.core_sparse and sparse.core_sparse
    return g, dense, sparse


@pytest.mark.parametrize("c, m_pad, k, sparse", [
    (26_953, 69_320, 16, True),     # road-grid-g256
    (26_694, 94_616, 16, True),     # web-pa-70k
    (22_980, 227_960, 16, True),    # social-pa-24k
    (2_809, 8_304, 16, False),      # the side-96 grid
    (332, 672, 16, False),          # the side-32 grid
])
def test_form_rule(c, m_pad, k, sparse):
    assert sparse_core_round(c, m_pad, k) is sparse


def test_sparse_engine_holds_no_dense_adjacency(forms):
    _, dense, sparse = forms
    c = sparse.index.n_core
    assert dense._core.shape == (c, c)
    assert sparse._core.shape == (0, 0)
    assert sparse.core_mode == dense.core_mode == "bellman"


def test_core_plan_destinations_ascend(forms):
    """The sparse round's scatter is told its indices are sorted."""
    assert np.all(np.diff(forms[2].index.plan_core.dst[0]) >= 0)


def test_rounds_agree_round_by_round(forms):
    _, dense, sparse = forms
    ix = sparse.index
    lo, c = ix.n_noncore, ix.n_core
    ends = jnp.asarray(ix.perm[SOURCES])
    state = sparse._run_plan(sparse._init_state(ends), sparse._plans.f,
                             sparse._relax_level)
    arc_round = jax.jit(QueryEngine._arc_round(sparse._plans.c))
    blocks = _block_rows(dense._core)
    d_sparse, d_dense = state, state[:, lo:lo + c]
    rounds, settle = 0, np.zeros(len(SOURCES), np.int32)
    while True:
        n_sparse = arc_round(d_sparse)
        n_dense = jnp.minimum(d_dense, _minplus_blocks(d_dense, blocks))
        np.testing.assert_array_equal(np.asarray(n_sparse[:, lo:lo + c]),
                                      np.asarray(n_dense))
        np.testing.assert_array_equal(np.asarray(n_sparse[:, :lo]),
                                      np.asarray(state[:, :lo]))
        moved = np.asarray(jnp.any(n_dense < d_dense, axis=1))
        rounds += 1
        settle[moved] = rounds
        d_sparse, d_dense = n_sparse, n_dense
        if not moved.any():
            break
    assert rounds > 2

    counts = [eng._ssd_jit(eng._plans, eng._core, ends)[1]
              for eng in (dense, sparse)]
    for form, cnt in enumerate(counts):
        assert int(cnt["core_rounds"]) == rounds
        np.testing.assert_array_equal(np.asarray(cnt["core_row_rounds"]),
                                      settle)
        assert int(cnt["core_sparse"]) == form


def test_sparse_answers_match_dijkstra_bit_for_bit(forms):
    g, dense, sparse = forms
    ref = dijkstra_reference(g, SOURCES)
    np.testing.assert_array_equal(sparse.ssd(SOURCES).astype(np.float64),
                                  ref)
    np.testing.assert_array_equal(
        sparse.p2p(SOURCES, TARGETS).astype(np.float64),
        ref[np.arange(len(SOURCES)), TARGETS])
    dist, pred = sparse.sssp(SOURCES)
    np.testing.assert_array_equal(dist.astype(np.float64), ref)
    np.testing.assert_array_equal(pred, dense.sssp(SOURCES)[1])
    np.testing.assert_array_equal(sparse.ssd_within(SOURCES, 9.0),
                                  dense.ssd_within(SOURCES, 9.0))


@pytest.mark.parametrize("mode", ["ssd", "p2p"])
def test_sparse_loop_reads_no_dense_core_and_no_plan_relayout(forms, mode):
    """Traced with a ``[C, C]`` core operand, the sparse program never
    reads it, and its one ``while`` holds a gather and a scatter-min but
    no pad, transpose or reshape of an array as large as the plan."""
    _, _, eng = forms
    c = eng.index.n_core
    pc = eng.index.plan_core
    plan_size = pc.m_pad * pc.k_fix
    core = jax.ShapeDtypeStruct((c, c), jnp.float32)
    ends = jnp.zeros(4, jnp.int32)
    if mode == "ssd":
        closed = jax.make_jaxpr(eng._ssd_impl, static_argnums=3)(
            eng._plans, core, ends, "bellman")
    else:
        closed = jax.make_jaxpr(eng._p2p_impl, static_argnums=4)(
            eng._plans, core, ends, ends, "bellman")
    n_plan = len(jax.tree.leaves(eng._plans))
    core_var = closed.jaxpr.invars[n_plan]
    assert core_var.aval.shape == (c, c)
    assert all(core_var not in e.invars for e in _eqns(closed.jaxpr))

    loops = [e for e in _eqns(closed.jaxpr) if e.primitive.name == "while"]
    assert len(loops) == 1
    body = [e for sub in _subjaxprs(loops[0]) for e in _eqns(sub)]
    names = {e.primitive.name for e in body}
    assert {"gather", "scatter-min"} <= names
    relayouts = [str(e.primitive) for e in body
                 if e.primitive.name in RESHAPES
                 and any(np.prod(v.aval.shape) >= plan_size
                         for v in e.invars if hasattr(v.aval, "shape"))]
    assert relayouts == []


def test_traced_call_carries_core_sparse(forms):
    _, dense, sparse = forms
    for form, eng in enumerate((dense, sparse)):
        eng.tracer = Tracer()
        try:
            with eng.tracer.bind(batch=1, fill=len(SOURCES)):
                eng.p2p(SOURCES, TARGETS)
            spans = eng.tracer.spans()
        finally:
            eng.tracer = None
        assert spans and all(s["args"]["core_sparse"] == form
                             for s in spans)


def test_streaming_engine_keeps_the_dense_round(forms, tmp_path,
                                                monkeypatch):
    """The store-backed engine has no ``plan_core`` on the device, so its
    bellman search stays dense whatever the rule says."""
    _, _, sparse = forms
    path = str(tmp_path / "store")
    sparse.index.save_store(path, block_bytes=1024)
    monkeypatch.setattr(query, "SPARSE_CORE_RATIO", 0)
    seng = StreamingQueryEngine(IndexStore(path), core_mode="bellman")
    try:
        c = seng.index.n_core
        assert not seng.core_sparse and seng._core.shape == (c, c)
        np.testing.assert_array_equal(seng.ssd(SOURCES), sparse.ssd(SOURCES))
    finally:
        seng.close()
