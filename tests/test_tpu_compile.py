"""Ahead-of-time compiles of the served path for a described TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
refuses what the Pallas interpreter accepts: a lane-axis slice it cannot
prove aligned, a kernel it cannot partition, a program that does not fit.
These tests compile the main path's kernels at the widths the side-256
road grid serves (S=16 sources, forward plan M=36,904 rows of K=16), and
the engine's SSD program on one chip and on a 2x2 data mesh.

The topology is described inside a module fixture, never at import: only
the test worker that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import shardlib as sl
from repro.core import QueryEngine, grid_road_graph
from repro.launch.serve import build_served_index

# Side-256 road grid served with the serve CLI's build (chip_smoke.py).
S, N_PAD, M_FWD, K = 16, 65537, 36904, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_edge_relax_compiles_at_side256_width(one_chip):
    from repro.kernels.edge_relax.ops import relax_bucketed
    args = (_sds((S, N_PAD), jnp.float32, one_chip),
            _sds((M_FWD, K), jnp.int32, one_chip),
            _sds((M_FWD, K), jnp.float32, one_chip),
            _sds((S, M_FWD), jnp.float32, one_chip),
            _sds((M_FWD,), jnp.bool_, one_chip))
    compiled = relax_bucketed.lower(*args, use_pallas=True,
                                    interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c", [40, 1000, 2048])
def test_minplus_compiles_up_to_closure_limit(one_chip, c):
    """Core sizes up to ``pack_index``'s ``closure_limit`` (2048), where
    the engine runs the closure kernel; 40 is narrower than one lane
    tile."""
    from repro.kernels.tropical_matmul.ops import minplus
    compiled = minplus.lower(_sds((S, c), jnp.float32, one_chip),
                             _sds((c, c), jnp.float32, one_chip),
                             interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _ssd_operands(eng, sharding):
    """The engine's SSD operands as shapes: plans, core matrix, sources."""
    plans = jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding),
                         eng._plans)
    c = eng.index.n_core
    return plans, _sds((c, c), jnp.float32, sharding), \
        _sds((S,), jnp.int32, sharding)


def _engine(side: int) -> QueryEngine:
    ix, _ = build_served_index(grid_road_graph(side, seed=1))
    return QueryEngine(ix, core_mode="bellman", use_pallas=True,
                       interpret=False)


def test_ssd_program_size_does_not_grow_with_index(one_chip):
    """The index reaches the compiled SSD program as operands: its HLO
    has the same size at side 32 (1,024 nodes) as at side 96 (9,216).
    Embedded as constants, side 96 alone lowered to 85 MB of text."""
    sizes = {}
    for side in (32, 96):
        eng = _engine(side)
        lowered = eng._ssd_jit.lower(*_ssd_operands(eng, one_chip))
        sizes[side] = len(lowered.as_text())
        if side == 96:
            assert "tpu_custom_call" in lowered.compile().as_text()
    assert sizes[96] <= 1.05 * sizes[32], sizes


def test_ssd_compiles_batch_sharded_on_2x2(topo, one_chip):
    """``serve --data-parallel``: the SSD batch sharded over a 4-chip
    ``data`` mesh compiles (the Pallas kernel runs per shard) and leaves
    each chip a quarter of the ``[S, n]`` answer."""
    eng = _engine(32)
    # The program's answer alone, without its counts.
    state = jax.jit(lambda *operands: eng._ssd_jit(*operands)[0])
    single = state.lower(*_ssd_operands(eng, one_chip)).compile()
    mesh = sl.make_mesh((4,), ("data",), devices=topo.devices)
    with sl.axis_rules(mesh, {"batch": "data"}):
        sharded = state.lower(
            *_ssd_operands(eng, NamedSharding(mesh, P()))).compile()
    out = sharded.output_shardings
    assert out.spec == P("data")
    assert out.shard_shape((S, eng.index.n)) == (S // 4, eng.index.n)
    assert sharded.memory_analysis().output_size_in_bytes == \
        single.memory_analysis().output_size_in_bytes // 4
