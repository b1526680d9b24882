"""ISSUE-10 sharded serving fleet: routing edge cases (DESIGN.md §13).

The structural claim under test: a fleet partitions *storage*, not
*math* — so answers are bit-identical to a single host at every shard
count and under every degenerate block layout, and a shard-local
fault travels the same path back into the query thread as a
single-host fault would.
"""
import os
import tempfile

import numpy as np
import pytest

from repro import shardlib as sl
from repro.core import (BuildConfig, build_hod, gnm_random_digraph,
                        pack_index)
from repro.fleet import (REPLICATED_SEGMENTS, ServingFleet,
                         StorePartition, split_budget)
from repro.storage import (IndexStore, PageCache, StreamingQueryEngine,
                           segment_bytes)

CFG = BuildConfig(max_core_nodes=32, max_core_edges=1024, seed=0)


@pytest.fixture(scope="module")
def packed():
    g = gnm_random_digraph(150, 600, seed=4, weighted=True)
    res = build_hod(g, CFG)
    ix = pack_index(g, res, chunk=64)
    return g, ix


@pytest.fixture(scope="module")
def store_dir(packed):
    _, ix = packed
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "store")
        ix.save_store(path, block_bytes=1024, codec="delta")
        yield path


def _solo_engine(store_dir, budget):
    store = IndexStore(store_dir, cache=PageCache(budget, policy="2q"))
    return StreamingQueryEngine(store, queue_depth=4)


def _fleet_engine(store_dir, n, budget, **kw):
    fleet = ServingFleet(store_dir, n, cache_bytes=budget, **kw)
    return StreamingQueryEngine(fleet.store, queue_depth=4), fleet


# ------------------------------------------------------------ partition
def test_partition_ranges_are_contiguous_and_balanced():
    part = StorePartition({"plan_f": 10, "plan_b": 7, "plan_core": 3}, 4)
    for name, n_blocks in (("plan_f", 10), ("plan_b", 7)):
        owners = [part.owner(name, b) for b in range(1, n_blocks + 1)]
        assert owners == sorted(owners)          # contiguous ranges
        assert set(owners) == set(range(4))      # every shard owns some
        counts = [owners.count(s) for s in range(4)]
        assert max(counts) - min(counts) <= 1    # balanced by count
        # local ids are dense and 1-based within each shard's range
        for s in range(4):
            locals_ = [part.local_block(name, b) % (1 << 40)
                       for b in range(1, n_blocks + 1)
                       if part.owner(name, b) == s]
            assert locals_ == list(range(1, len(locals_) + 1))
    # the pinned tier is replicated: materialized home is shard 0
    assert "plan_core" in REPLICATED_SEGMENTS
    assert all(part.owner("plan_core", b) == 0 for b in (1, 2, 3))
    assert "replicated" in part.describe()


def test_partition_rejects_out_of_range_blocks():
    part = StorePartition({"plan_f": 5}, 2)
    with pytest.raises(ValueError, match="out of range"):
        part.owner("plan_f", 0)
    with pytest.raises(ValueError, match="out of range"):
        part.owner("plan_f", 6)
    with pytest.raises(ValueError, match="unknown segments"):
        StorePartition({"bogus": 5}, 2)


def test_partition_empty_shard_when_n_exceeds_blocks():
    part = StorePartition({"plan_f": 2}, 4)
    owners = {part.owner("plan_f", b) for b in (1, 2)}
    assert len(owners) == 2
    empty = set(range(4)) - owners
    assert empty                                 # some shards own nothing
    for s in empty:
        assert part.shard_blocks(s) == 0


def test_split_budget():
    assert split_budget(None, 3, 1024) == [None, None, None]
    # degenerate fleet keeps the exact budget (counter parity with an
    # unsharded server depends on it)
    assert split_budget(10_001, 1, 1024) == [10_001]
    # N>1 rounds UP to whole blocks, never down
    per = split_budget(10_000, 3, 1024)
    assert per == [4096, 4096, 4096]
    assert all(b % 1024 == 0 and b * 3 >= 10_000 for b in per)
    # budget is proportional to owned footprint (replicated segments
    # count toward shard 0, so its materialized core copy is funded by
    # its larger share rather than a side-channel)
    prop = split_budget(12_000, 2, 1024, owned_blocks=[3, 1])
    assert prop == [9216, 3072]  # ceil of 9000 / 3000 to whole blocks
    # a shard that owns nothing still gets a nominal slice (it serves
    # no traffic, so the slice is never resident)
    assert split_budget(12_000, 2, 1024, owned_blocks=[4, 0]) \
        == [12288, 3072]
    # a floor raises a shard's slice (the replicated tier's home must
    # hold the whole tier or every query thrashes it) without touching
    # the others
    assert split_budget(12_000, 2, 1024, owned_blocks=[3, 1],
                        floors=[10_000, 0]) == [10_240, 3072]


# ------------------------------------------------------ degenerate fleets
def test_n1_fleet_matches_plain_server(store_dir):
    budget = int(0.25 * segment_bytes(store_dir))
    srcs = np.arange(0, 150, 7, dtype=np.int32)
    solo = _solo_engine(store_dir, budget)
    feng, fleet = _fleet_engine(store_dir, 1, budget)
    try:
        want = solo.ssd(srcs)
        got = feng.ssd(srcs)
        np.testing.assert_array_equal(want, got)
        ss, fs = solo.store.cache.stats, fleet.store.cache.stats
        for field in ("hits", "misses", "bytes_read", "bytes_filled"):
            assert getattr(fs, field) == getattr(ss, field), field
    finally:
        solo.close()
        feng.close()
    assert fleet._workers_down      # engine close shut the shard workers


def test_all_blocks_on_one_shard_still_bit_identical(store_dir):
    """owner_fn forces every partitioned block onto shard 0: shard 1
    is pure dead weight, but routing through it must not change a
    single answer, and it must see zero traffic."""
    budget = int(0.25 * segment_bytes(store_dir))
    srcs = np.arange(0, 150, 11, dtype=np.int32)
    solo = _solo_engine(store_dir, budget)
    feng, fleet = _fleet_engine(store_dir, 2, budget,
                                owner_fn=lambda name, block: 0)
    try:
        np.testing.assert_array_equal(solo.ssd(srcs), feng.ssd(srcs))
        idle = fleet.shards[1].cache.stats
        assert (idle.hits, idle.misses, idle.bytes_read) == (0, 0, 0)
        assert fleet.shards[0].cache.stats.misses > 0
    finally:
        solo.close()
        feng.close()


def test_sources_landing_on_empty_shard(packed, tmp_path):
    """More shards than any segment has blocks: the tail shards own
    empty ranges.  Every source — including ones whose sweep would hash
    to those shards — must still answer bit-identically."""
    _, ix = packed
    path = str(tmp_path / "store")
    ix.save_store(path, block_bytes=16384, codec="delta")
    probe = IndexStore(path)
    n = max(probe.segment_blocks().values()) + 1
    probe.close()
    budget = int(0.25 * segment_bytes(path))
    srcs = np.arange(0, 150, 5, dtype=np.int32)
    solo = _solo_engine(path, budget)
    feng, fleet = _fleet_engine(path, n, budget)
    try:
        assert any(fleet.partition.shard_blocks(s) == 0
                   for s in range(n)), "want at least one empty shard"
        np.testing.assert_array_equal(solo.ssd(srcs), feng.ssd(srcs))
        stats = fleet.stats()
        assert sum(r["bytes_read"] for r in stats.rows) == \
            stats.cache.bytes_read
        for r in stats.rows:
            if r["blocks"] == 0:
                assert r["hits"] + r["misses"] == 0
    finally:
        solo.close()
        feng.close()


# ----------------------------------------------------- fault propagation
def test_shard_worker_crc_error_raises_in_query_thread(packed, tmp_path):
    """A corrupt frame decoded on a *shard's* decode pool at N=2 must
    surface in the querying thread exactly like the single-host
    pipeline fault (test_pipeline), and stay repeatable — the poisoned
    placeholder is discarded, not stuck."""
    _, ix = packed
    path = str(tmp_path / "store")
    ix.save_store(path, block_bytes=1024, codec="delta")
    seg = os.path.join(path, "plan_f.seg")
    with open(seg, "r+b") as f:
        f.seek(2 * 1024 + 100)
        f.write(b"\xde\xad\xbe\xef" * 8)
    feng, _ = _fleet_engine(path, 2, None, decode_workers=2)
    try:
        with pytest.raises(ValueError, match="CRC mismatch"):
            feng.ssd(np.array([0], dtype=np.int32))
        with pytest.raises(ValueError, match="CRC mismatch"):
            feng.ssd(np.array([0], dtype=np.int32))
    finally:
        feng.close()


# ------------------------------------------------------------- shardlib
def test_pmin_identity_without_axes_and_under_1_device_mesh():
    from jax.sharding import PartitionSpec as P

    x = np.array([3.0, 1.0, 2.0], np.float32)
    np.testing.assert_array_equal(sl.pmin(x, ()), x)
    mesh = sl.make_mesh((1,), ("data",))
    with sl.axis_rules(mesh, {"batch": "data"}):
        out = sl.maybe_shard_map(
            lambda v: sl.pmin(v, ("data",)),
            in_specs=(P("data"),), out_specs=P())(x)
    np.testing.assert_array_equal(np.asarray(out), x)
