"""The bellman core search prepares its constant adjacency outside the
round loop.

Every bellman round multiplies the same ``[C, C]`` core matrix.  Any
pad, transpose or reshape of a whole matrix inside the ``while`` body
would run once per round on the chip (XLA does not hoist such ops), so
the SSD and P2P programs are checked for none.  The min-plus itself
keeps its arithmetic: it equals a NumPy min-plus exactly, and bellman
answers equal Dijkstra's bit for bit on a core that is not a whole
number of k blocks.
"""
import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr
import numpy as np
import pytest

from repro.core import (BuildConfig, QueryEngine, build_hod,
                        dijkstra_reference, gnm_random_digraph,
                        grid_road_graph)
from repro.core.index import pack_index
from repro.core.query import _minplus_blocked
from repro.launch.serve import build_served_index

RESHAPES = {"pad", "transpose", "reshape"}


@pytest.fixture(scope="module")
def grid():
    """Side-32 road grid: a 332-node core, one whole k block and a
    76-row tail."""
    g = grid_road_graph(32, seed=1)
    ix, _ = build_served_index(g)
    assert ix.n_core == 332
    return g, QueryEngine(ix, core_mode="bellman")


@pytest.fixture(scope="module")
def engines(grid):
    """Bellman engines whose cores are and are not whole k blocks."""
    g = gnm_random_digraph(300, 9000, seed=0, weighted=True)
    res = build_hod(g, BuildConfig(max_core_nodes=256,
                                   max_core_edges=1 << 17, seed=0))
    ix = pack_index(g, res, chunk=64, closure_limit=8)
    assert ix.n_core == 256
    return {"core332": grid[1],
            "core256": QueryEngine(ix, core_mode="bellman")}


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from _eqns(sub)


def _whole_matrix_reshapes(closed, c):
    """``(loops, ops)``: the ``while`` loops of a program, and the pad,
    transpose and reshape ops inside them whose operand holds at least
    ``c * c`` elements."""
    loops = [e for e in _eqns(closed.jaxpr) if e.primitive.name == "while"]
    ops = [str(e.primitive) for loop in loops
           for sub in _subjaxprs(loop) for e in _eqns(sub)
           if e.primitive.name in RESHAPES
           and any(np.prod(v.aval.shape) >= c * c for v in e.invars
                   if hasattr(v.aval, "shape"))]
    return loops, ops


@pytest.mark.parametrize("core", ["core332", "core256"])
@pytest.mark.parametrize("mode", ["ssd", "p2p"])
def test_bellman_loop_holds_no_whole_matrix_reshape(engines, core, mode):
    eng = engines[core]
    c = eng.index.n_core
    ends = jnp.zeros(4, jnp.int32)
    if mode == "ssd":
        closed = jax.make_jaxpr(eng._ssd_impl, static_argnums=3)(
            eng._plans, eng._core, ends, "bellman")
    else:
        closed = jax.make_jaxpr(eng._p2p_impl, static_argnums=4)(
            eng._plans, eng._core, ends, ends, "bellman")
    loops, ops = _whole_matrix_reshapes(closed, c)
    assert len(loops) == 1
    assert ops == []


@pytest.mark.parametrize("k", [1, 255, 256, 513])
def test_blocked_minplus_equals_numpy_exactly(k):
    rng = np.random.default_rng(k)
    a = rng.integers(0, 100, (5, k)).astype(np.float32)
    b = rng.integers(1, 50, (k, 37)).astype(np.float32)
    a[rng.random(a.shape) < 0.3] = np.inf
    b[rng.random(b.shape) < 0.5] = np.inf
    a += rng.random(a.shape).astype(np.float32)   # fractional f32 sums
    want = np.min(a[:, :, None] + b[None], axis=1)
    np.testing.assert_array_equal(
        np.asarray(_minplus_blocked(jnp.asarray(a), jnp.asarray(b))), want)


def test_bellman_ssd_and_p2p_match_dijkstra_bit_for_bit(grid):
    g, eng = grid
    sources = np.array([0, 5, 333, 511, 700, 1023], np.int32)
    targets = np.array([1023, 900, 17, 512, 0, 31], np.int32)
    ref = dijkstra_reference(g, sources)
    np.testing.assert_array_equal(eng.ssd(sources).astype(np.float64), ref)
    np.testing.assert_array_equal(
        eng.p2p(sources, targets).astype(np.float64),
        ref[np.arange(len(sources)), targets])
