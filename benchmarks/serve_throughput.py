"""Batched serving throughput: queries/sec + disk I/O per batch size,
and the memory-constrained store regime — the two claims behind the
serving design (DESIGN.md §6–§8):

* **amortization**: every source in a batch shares one sequential index
  scan, so modeled I/O per query falls linearly with batch size while
  measured throughput rises until the sweeps saturate the device;
* **disk residency**: a store-backed server answers the same queries
  while holding only ``cache_bytes`` of the index resident; sweeping
  the budget over {5%, 25%, 100%} of the segment bytes reproduces the
  paper's memory-constrained regime — the device then meters *actual*
  block reads (cache misses), so hit-rate and measured I/O seconds vary
  with the budget instead of being a fixed synthetic charge.  The
  ``codec`` column (format v5, DESIGN.md §6) re-runs the budget sweep
  from ``delta``/``f16`` compressed stores at the same decompressed
  cache budget: identical hit sequence, strictly fewer compressed
  bytes read — the paper's on-disk-size currency, measured.  The
  ``queue_depth`` table (ISSUE-7) re-runs the cold 25% point through
  the depth-N read pipeline: same bytes at every depth (asserted —
  cache transactions are submit-ordered), strictly less modeled stall
  at depth >= 4.

Also reports the cold-start path the SweepPlan is for (DESIGN.md §5):
index ``.npz`` load → engine construction → warm-start compile → first
answered request, in wall-clock ms.  Since the plan is persisted in the
index file, load never re-derives the bucketed layout, and the executor
compiles O(1) traces regardless of level count.

``run()`` returns its tables as metric-dict rows;
``benchmarks/run.py`` persists them to ``BENCH_serve.json``.

    PYTHONPATH=src python -m benchmarks.run --tables serve
"""
from __future__ import annotations

import asyncio
import os
import tempfile
import time

import numpy as np

from repro.config import Config
from repro.core import QueryEngine
from repro.core.index import HoDIndex
from repro.launch.serve import QueryServer, mixed_request_stream
from repro.storage import segment_bytes

from .common import build_hod_cached, dataset_suite, fmt_row

BATCH_SIZES = (1, 16, 128)
N_REQUESTS = 256
COLD_BATCH = 16
#: (cache fraction, eviction policy) grid: the 2q sweep reproduces the
#: memory-constrained regime under the scan-resistant default, the lru
#: row at 25% keeps the PR-3 thrash baseline measurable next to it.
STORE_CONFIGS = ((0.05, "2q"), (0.25, "lru"), (0.25, "arc"),
                 (0.25, "2q"), (1.0, "2q"))
#: codec × budget grid (policy 2q).  Budgets are fractions of the RAW
#: store's segment bytes for every codec, so each (frac, codec) cell
#: holds the same number of decompressed blocks — the hit sequence is
#: identical and the codec column isolates compressed bytes-read.
STORE_CODECS = ("delta", "f16")
CODEC_FRACS = (0.05, 0.25, 1.0)
#: ISSUE-5 acceptance: delta segments must undercut raw by >= 30%.
DELTA_MIN_SHRINK = 0.30
STORE_BATCH = 16
STORE_REQUESTS = 64
#: ISSUE-7 read-pipeline grid: queue depth x codec at the 25% 2q
#: budget.  Depth 1 is the no-read-ahead baseline; the determinism
#: design (cache transactions at submit time, in block order) means
#: every depth reads the same bytes, so stall seconds is the only
#: axis that moves.
QUEUE_DEPTHS = (1, 2, 4, 8)
QD_CODECS = ("raw", "delta", "f16")
QD_FRAC = 0.25
QD_DECODE_WORKERS = 2
#: ISSUE-8 latency table: modes served traced + untraced from the 25%
#: 2q store at depth 4.  Tracing must cost < 5% engine-busy time (plus
#: a small absolute slack for timer noise on these millisecond runs),
#: asserted on the min of ``OVERHEAD_REPEATS`` warm repeats.
LATENCY_MODES = ("ssd", "p2p")
TRACE_OVERHEAD_FRAC = 0.05
TRACE_OVERHEAD_SLACK_S = 0.002
OVERHEAD_REPEATS = 3
#: ISSUE-9 slo table: both policies must serve the same offered load
#: at matching wall-clock throughput (the p99 win can't come from
#: shedding work).
SLO_QPS_TOL = 0.25
#: ISSUE-10 fleet grid: shard counts served from one cold store.  The
#: raw codec is deliberate: bytes_read is then a pure function of miss
#: counts, which makes the "N>1 reads no more than N=1" gate
#: structural (a compressing codec lands equal miss counts on
#: different-sized blocks).
FLEET_SHARDS = (1, 2, 4)
FLEET_FRAC = 0.25
FLEET_QPS_TOL = 0.5

#: The declarative grid (DESIGN.md §12): ``run()`` loads
#: ``configs/bench_serve.yaml`` when present, layered over these
#: defaults — which mirror the historical module constants so rows
#: stay comparable when the file is absent.
BENCH_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            os.pardir, "configs", "bench_serve.yaml")
BENCH_DEFAULTS = {
    "bench": {
        "batch_sizes": list(BATCH_SIZES),
        "n_requests": N_REQUESTS,
        "store": {
            "requests": STORE_REQUESTS,
            "cache_grid": [list(fp) for fp in STORE_CONFIGS],
            "codecs": list(STORE_CODECS),
            "codec_fracs": list(CODEC_FRACS),
        },
        "queue_depth": {"depths": list(QUEUE_DEPTHS),
                        "codecs": list(QD_CODECS)},
        "fleet": {
            "shard_counts": list(FLEET_SHARDS),
            "requests": STORE_REQUESTS,
            "cache_frac": FLEET_FRAC,
            "policy": "2q",
            "codec": "raw",
            "qps_tol": FLEET_QPS_TOL,
        },
        "latency": {"modes": list(LATENCY_MODES)},
        "slo": {
            "requests": 256, "rate": 250.0, "batch": 16,
            "max_wait_ms": 60.0, "p2p_pool": 16,
            "mix": {"ssd": 1, "p2p": 3},
            "classes": {"ssd": {"deadline_ms": 200.0},
                        "p2p": {"deadline_ms": 60.0, "batch": 8}},
        },
    },
}


def load_bench_config(path: str | None = None) -> Config:
    """``configs/bench_serve.yaml`` (with its ``_include`` chain)
    layered over :data:`BENCH_DEFAULTS`; a missing file is fine, a
    present-but-broken one is a loud ``ConfigError``."""
    path = path if path is not None else (
        BENCH_CONFIG if os.path.exists(BENCH_CONFIG) else None)
    return Config(path, defaults=BENCH_DEFAULTS)


def cold_start_latency(ix) -> dict:
    """Measure index-load → first-response wall time via a real save/load
    round trip (the restart path a serving fleet actually takes)."""
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "index.npz")
        ix.save(path)
        t0 = time.perf_counter()
        loaded = HoDIndex.load(path)
        t_load = time.perf_counter() - t0
        engine = QueryEngine(loaded)
        server = QueryServer(engine, batch_size=COLD_BATCH,
                             cache_entries=0, warm_start=True)
        t_warm = time.perf_counter() - t0
        server.serve_stream(np.zeros(1, dtype=np.int32))
        t_first = time.perf_counter() - t0
    return {"load_s": t_load, "warm_s": t_warm, "first_s": t_first}


def _serve_store(store_dir: str, budget: int, policy: str,
                 sources: np.ndarray, n: int):
    server = QueryServer(store_path=store_dir, cache_bytes=budget,
                         batch_size=STORE_BATCH, cache_entries=0,
                         cache_policy=policy, warm_start=True)
    try:
        results = server.serve_stream(sources)
    finally:
        server.close()
    assert all(np.isfinite(r.dist[:n]).all() for r in results)
    return server


def store_cache_sweep(ix, sources: np.ndarray, *,
                      cache_grid=STORE_CONFIGS, codecs=STORE_CODECS,
                      codec_fracs=CODEC_FRACS) -> list:
    """Serve the same request stream from a block store under the
    (page-cache budget, eviction policy) grid of ``STORE_CONFIGS``,
    then under the codec × budget grid of ``STORE_CODECS``.

    The scan-resistant policies + the v4 affinity layout are what make
    the mid-budget rows meaningful: under PR-3's LRU + block-aligned
    slabs the 5%/25% rows thrashed to a 0.0 hit rate.  The codec rows
    (format v5) hold the decompressed cache budget fixed per fraction,
    so ``real_bytes`` isolates what compression buys: compressed
    bytes-read strictly below the raw row at every budget."""
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        store_dir = os.path.join(tmp, "store")
        ix.save_store(store_dir)
        seg_bytes = segment_bytes(store_dir)
        print(f"\n-- store-backed serving: {seg_bytes/1e6:.2f} MB of "
              f"segments, {sources.shape[0]} requests, "
              f"batch={STORE_BATCH} --")
        print(fmt_row(["codec", "cache", "policy", "hit rate", "real MB",
                       "modeled MB", "io ms", "queries/s"]))

        def one_row(codec, sdir, frac, policy):
            budget = int(frac * seg_bytes)   # raw-store denominator
            server = _serve_store(sdir, budget, policy, sources, ix.n)
            st = server.stats
            io = server.modeled_io()
            io_s = io.modeled_seconds(
                block_bytes=server.device.block_bytes)
            modeled_mb = server.modeled_scan_bytes * st.batches / 1e6
            print(fmt_row([
                codec, f"{frac:.0%}", policy,
                f"{st.page_hit_rate():.1%}",
                f"{st.store_bytes_read/1e6:.2f}", f"{modeled_mb:.2f}",
                f"{io_s*1e3:.1f}", f"{st.throughput():.0f}"]))
            rows.append({
                "codec": codec, "cache_frac": frac, "policy": policy,
                "cache_bytes": budget,
                "seg_bytes": segment_bytes(sdir),
                "hit_rate": st.page_hit_rate(),
                "real_bytes": st.store_bytes_read,
                "filled_bytes": st.store_bytes_filled,
                "modeled_bytes": server.modeled_scan_bytes * st.batches,
                "io_seconds": io_s, "queries_per_s": st.throughput(),
                "seq_blocks": io.seq_blocks, "rand_blocks": io.rand_blocks,
            })
            return rows[-1]

        raw_rows = {}
        for frac, policy in cache_grid:
            row = one_row("raw", store_dir, frac, policy)
            if policy == "2q":
                raw_rows[frac] = row
        for codec in codecs:
            cdir = os.path.join(tmp, f"store_{codec}")
            ix.save_store(cdir, codec=codec)
            cseg = segment_bytes(cdir)
            if codec == "delta":
                assert cseg <= (1 - DELTA_MIN_SHRINK) * seg_bytes, (
                    f"delta segments {cseg} shrank segment bytes by "
                    f"less than {DELTA_MIN_SHRINK:.0%} vs raw {seg_bytes}")
            for frac in codec_fracs:
                row = one_row(codec, cdir, frac, "2q")
                raw_read = raw_rows[frac]["real_bytes"]
                # fully-resident budgets read nothing after warmup on
                # either store; every partial budget must read strictly
                # fewer compressed bytes than raw
                assert (row["real_bytes"] < raw_read if raw_read
                        else row["real_bytes"] == 0), (
                    f"{codec}@{frac:.0%}: compressed bytes-read "
                    f"{row['real_bytes']} not below raw {raw_read}")
    return rows


def queue_depth_sweep(ix, sources: np.ndarray, *,
                      depths=QUEUE_DEPTHS, codecs=QD_CODECS) -> list:
    """ISSUE-7: serve a cold 25% 2q store at every (codec, queue depth)
    cell and meter the read pipeline's overlap.

    Every server warm-starts (jit compiled off the clock), then the
    page cache is cleared so the request stream runs against a cold
    store.  Because cache transactions happen at submit time in block
    order, the hit/miss/bytes-read sequence is *identical* at every
    depth (asserted) — the depth axis moves only the stall columns:
    ``stall_model_s`` is the discrete-event model of the consumer
    waiting on the one-spindle device (deterministic, comparable across
    runs), ``queries_per_s`` is the modeled-basis throughput
    ``requests / (compute + modeled stall)``, and ``wall_*`` the raw
    measured numbers.  The compute term is held at the codec's depth-1
    measurement for every depth, so the column isolates the overlap
    win instead of re-measuring jit dispatch noise per row (each row's
    own measurement still lands in ``compute_s`` /
    ``wall_queries_per_s``).  Depth >= 4 must strictly cut modeled
    stall and beat depth 1's modeled throughput at every codec.

    Tail checks: depth-4 SSD/SSSP/P2P answers are bit-identical to the
    synchronous (``prefetch=False``) path, and the bounded p2p sweep
    still provably skips device reads when run through a pipelined
    engine."""
    from repro.storage import IndexStore, PageCache, StreamingQueryEngine

    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        dirs = {}
        for codec in codecs:
            d = os.path.join(tmp, f"store_{codec}")
            ix.save_store(d, codec=codec)
            dirs[codec] = d
        budget = int(QD_FRAC * segment_bytes(dirs["raw"]))
        print(f"\n-- read-pipeline queue-depth sweep: cold "
              f"{QD_FRAC:.0%} 2q store, {sources.shape[0]} requests, "
              f"batch={STORE_BATCH}, {QD_DECODE_WORKERS} decode "
              f"workers --")
        print(fmt_row(["codec", "depth", "hit rate", "real MB",
                       "stall ms", "wall-stall ms", "ttfl ms",
                       "q/s (model)", "q/s (wall)"]))
        base = {}
        for codec in codecs:
            for depth in depths:
                server = QueryServer(
                    store_path=dirs[codec], cache_bytes=budget,
                    batch_size=STORE_BATCH, cache_entries=0,
                    cache_policy="2q", queue_depth=depth,
                    decode_workers=QD_DECODE_WORKERS, warm_start=True)
                try:
                    server.store.cache.clear()   # cold store, warm jit
                    results = server.serve_stream(sources)
                finally:
                    server.close()
                assert all(np.isfinite(r.dist[: ix.n]).all()
                           for r in results)
                st = server.stats
                compute = st.busy_seconds - st.stall_wall_seconds
                ref = (base[codec]["compute_s"] if codec in base
                       else compute)
                qps_model = st.requests / (ref + st.stall_seconds)
                row = {
                    "codec": codec, "queue_depth": depth,
                    "cache_frac": QD_FRAC, "policy": "2q",
                    "cache_bytes": budget,
                    "hit_rate": st.page_hit_rate(),
                    "real_bytes": st.store_bytes_read,
                    "filled_bytes": st.store_bytes_filled,
                    "stall_model_s": st.stall_seconds,
                    "stall_wall_s": st.stall_wall_seconds,
                    "ttfl_s": st.ttfl_seconds,
                    "compute_s": compute,
                    "queries_per_s": qps_model,
                    "wall_queries_per_s": st.throughput(),
                }
                rows.append(row)
                print(fmt_row([
                    codec, depth, f"{row['hit_rate']:.1%}",
                    f"{row['real_bytes']/1e6:.2f}",
                    f"{row['stall_model_s']*1e3:.1f}",
                    f"{row['stall_wall_s']*1e3:.1f}",
                    f"{row['ttfl_s']*1e3:.2f}",
                    f"{qps_model:.0f}", f"{st.throughput():.0f}"]))
                if depth == 1:
                    base[codec] = row
                    continue
                b = base[codec]
                # determinism: deeper queues read the SAME bytes
                assert (row["real_bytes"], row["hit_rate"]) == (
                    b["real_bytes"], b["hit_rate"]), (
                    f"{codec}@depth{depth}: cache sequence diverged "
                    f"from depth 1")
                if depth >= 4:
                    assert row["stall_model_s"] < b["stall_model_s"], (
                        f"{codec}@depth{depth}: modeled stall "
                        f"{row['stall_model_s']:.4f}s not below depth-1 "
                        f"{b['stall_model_s']:.4f}s")
                    assert row["queries_per_s"] > b["queries_per_s"], (
                        f"{codec}@depth{depth}: modeled throughput "
                        f"{row['queries_per_s']:.0f} q/s not above "
                        f"depth-1 {b['queries_per_s']:.0f}")

        # Bit-exactness + skip guarantee through the pipelined engine.
        from repro.core.index import node_levels

        sdir = dirs["delta"]
        s8 = sources[:8].astype(np.int32)
        t8 = s8[::-1].copy()

        def engine_for(prefetch, cache_bytes=budget):
            store = IndexStore(
                sdir, cache=PageCache(cache_bytes, policy="2q"))
            return StreamingQueryEngine(store, prefetch=prefetch,
                                        queue_depth=4)

        epipe, esync = engine_for(True), engine_for(False)
        try:
            assert np.array_equal(epipe.ssd(s8), esync.ssd(s8))
            dp, pp = epipe.sssp(s8)
            ds, ps = esync.sssp(s8)
            assert np.array_equal(dp, ds) and np.array_equal(pp, ps)
            assert np.array_equal(epipe.p2p(s8, t8), esync.p2p(s8, t8))
        finally:
            epipe.close()
            esync.close()

        store = IndexStore(sdir, cache=PageCache(0))
        eng = StreamingQueryEngine(store, queue_depth=4)
        try:
            lvl = node_levels(ix, np.arange(ix.n))[ix.perm]
            mid = np.nonzero((lvl > 0) & (lvl < ix.n_levels))[0]
            s1, t1 = (mid[:1].astype(np.int32),
                      mid[-1:].astype(np.int32))
            dev = store.device.stats
            b0 = dev.bytes_seq + dev.bytes_rand
            eng.ssd(s1)
            b_ssd = dev.bytes_seq + dev.bytes_rand - b0
            b1 = dev.bytes_seq + dev.bytes_rand
            eng.p2p(s1, t1)
            b_p2p = dev.bytes_seq + dev.bytes_rand - b1
        finally:
            eng.close()
        print(f"pipelined cold single-query sweep: p2p "
              f"{b_p2p/1e3:.0f} KB vs ssd {b_ssd/1e3:.0f} KB")
        assert 0 < b_p2p < b_ssd, (
            "bounded p2p sweep stopped skipping device reads under the "
            f"read pipeline: {b_p2p} vs {b_ssd}")
    return rows


def _fleet_sweep_once(ix, sources: np.ndarray, fleet_cfg: Config) -> list:
    shard_counts = [int(n) for n in fleet_cfg.get("shard_counts")]
    frac = float(fleet_cfg.get("cache_frac"))
    policy = str(fleet_cfg.get("policy"))
    codec = str(fleet_cfg.get("codec"))
    qps_tol = float(fleet_cfg.get("qps_tol"))

    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        store_dir = os.path.join(tmp, "store")
        ix.save_store(store_dir, codec=codec)
        budget = int(frac * segment_bytes(store_dir))
        print(f"\n-- sharded-fleet sweep: cold {frac:.0%} {policy} "
              f"{codec} store, {sources.shape[0]} requests, "
              f"batch={STORE_BATCH} --")
        print(fmt_row(["shards", "hit rate", "real MB", "stall ms",
                       "q/s (wall)", "per-shard hit rates"]))

        def serve(shards):
            server = QueryServer(
                store_path=store_dir, cache_bytes=budget,
                batch_size=STORE_BATCH, cache_entries=0,
                cache_policy=policy, queue_depth=4,
                decode_workers=QD_DECODE_WORKERS, warm_start=True,
                shards=shards)
            try:
                server.store.cache.clear()   # cold store, warm jit
                results = server.serve_stream(sources)
            finally:
                server.close()
            return results, server

        ref_results, ref_server = serve(None)
        ref_st = ref_server.stats
        ref = np.stack([r.dist for r in ref_results])

        solo_row = None
        for n in shard_counts:
            results, server = serve(n)
            got = np.stack([r.dist for r in results])
            assert np.array_equal(ref, got), (
                f"shards={n}: answers diverged from the unsharded "
                f"server — the fleet changed math, not just storage")
            st = server.stats
            fs = server.fleet_report()
            assert fs is not None and len(fs.rows) == n
            # routing accounting: per-shard bytes sum to the fleet
            # aggregate, and N>1 genuinely spreads traffic (a cold
            # bounded sweep can skip a small tail shard's blocks
            # entirely, so per-shard hit rates are the warm fleet
            # smoke's job, not this sweep's).
            assert sum(r["bytes_read"] for r in fs.rows) == \
                fs.cache.bytes_read, (
                f"shards={n}: per-shard bytes don't sum to the fleet "
                f"aggregate")
            served = sum(1 for r in fs.rows if r["hits"] + r["misses"])
            assert n == 1 or served >= 2, (
                f"shards={n}: only {served} shard(s) served traffic — "
                f"routing collapsed onto one shard")
            row = {
                "shards": n, "codec": codec, "cache_frac": frac,
                "policy": policy, "cache_bytes": budget,
                "hit_rate": st.page_hit_rate(),
                "real_bytes": st.store_bytes_read,
                "filled_bytes": st.store_bytes_filled,
                "stall_model_s": st.stall_seconds,
                "queries_per_s": st.throughput(),
                "shard_blocks": [r["blocks"] for r in fs.rows],
                "shard_hit_rates": [r["hit_rate"] for r in fs.rows],
                "shard_bytes": [r["bytes_read"] for r in fs.rows],
            }
            rows.append(row)
            print(fmt_row([
                n, f"{row['hit_rate']:.1%}",
                f"{row['real_bytes']/1e6:.2f}",
                f"{row['stall_model_s']*1e3:.1f}",
                f"{row['queries_per_s']:.0f}",
                " ".join(f"{h:.0%}" for h in row["shard_hit_rates"])]))
            if n == 1:
                solo_row = row
                # degenerate fleet: counter-for-counter the unsharded
                # server (split_budget keeps the exact budget at N=1).
                assert (row["real_bytes"], row["filled_bytes"],
                        row["hit_rate"]) == (
                    ref_st.store_bytes_read, ref_st.store_bytes_filled,
                    ref_st.page_hit_rate()), (
                    "shards=1: cache counters diverged from the "
                    "unsharded server — the routing façade changed "
                    "cache behavior")
            elif solo_row is not None:
                # structural under the raw codec (bytes are a pure
                # function of miss counts): sharding must not inflate
                # I/O — per-shard budgets round UP, never down.
                assert row["real_bytes"] <= solo_row["real_bytes"], (
                    f"shards={n} read {row['real_bytes']} bytes > "
                    f"shards=1's {solo_row['real_bytes']} — sharding "
                    f"must not inflate I/O")
            # wall-clock: a thread-backed fleet on one machine should
            # stay within qps_tol of the unsharded server (it does the
            # same compute; only storage bookkeeping moved).
            floor = (1.0 - qps_tol) * ref_st.throughput()
            assert row["queries_per_s"] >= floor, (
                f"shards={n}: wall throughput "
                f"{row['queries_per_s']:.0f} q/s below "
                f"{1.0 - qps_tol:.0%} of unsharded "
                f"{ref_st.throughput():.0f}")
    return rows


def fleet_sweep(ix, sources: np.ndarray, fleet_cfg: Config) -> list:
    """ISSUE-10: the sharded-fleet table — one row per shard count from
    the same cold raw store, with the acceptance invariants asserted
    in-sweep: bit-identical answers at every N, exact counter equality
    for the N=1 degenerate fleet, per-shard hit rates > 0 wherever a
    shard owns blocks, and no I/O inflation at N>1.  The wall-clock
    throughput floor is the only timing-sensitive check, so the sweep
    runs under :func:`_timing_retry`; the recorded rows are gated with
    configurable tolerances by ``check_regression.py``."""
    return _timing_retry(lambda: _fleet_sweep_once(ix, sources,
                                                   fleet_cfg),
                         label="fleet sweep")


#: ISSUE-6 workload classes served from one 25% 2q raw store: full SSD
#: sweeps, pure point-to-point pairs, and an alternating 50/50 mix.
WORKLOADS = ("ssd", "p2p", "mixed")


def workload_mix_sweep(ix, sources: np.ndarray) -> list:
    """Serve the ISSUE-6 workload classes and meter each one's real I/O.

    All three classes run the same request count from identically
    configured cold stores (25% budget, 2q, raw codec).  The p2p class
    answers ``(source, target)`` pairs by meet-in-the-middle: a *cold*
    p2p sweep provably reads fewer bytes than a cold full sweep (its
    halves skip plan levels below the query endpoints and can stop on
    the meet bound) — metered as ``cold_query_bytes`` per row and
    asserted.  The *stream* ``real_bytes`` under a warm 25% cache is
    reported unasserted: batched random pairs rarely share a high
    minimum endpoint level, and the reversed ``plan_b`` walk shifts
    which blocks stay hot, so aggregate misses can go either way."""
    from repro.storage import IndexStore, PageCache, StreamingQueryEngine

    rng = np.random.default_rng(1)
    targets = rng.integers(0, ix.n, size=sources.shape[0]).astype(np.int32)
    pairs = np.stack([sources, targets], axis=1)
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        store_dir = os.path.join(tmp, "store")
        ix.save_store(store_dir)
        budget = int(0.25 * segment_bytes(store_dir))
        print(f"\n-- workload mix: {sources.shape[0]} requests each from "
              f"a 25% 2q store, batch={STORE_BATCH} --")
        print(fmt_row(["workload", "hit rate", "real MB", "modeled MB",
                       "queries/s"]))
        for wl in WORKLOADS:
            store = IndexStore(store_dir,
                               cache=PageCache(budget, policy="2q"))
            engine = StreamingQueryEngine(store)
            modes = {"ssd": ("ssd",), "p2p": ("p2p",),
                     "mixed": ("ssd", "p2p")}[wl]
            servers = {m: QueryServer(engine, batch_size=STORE_BATCH,
                                      cache_entries=0, mode=m,
                                      device=store.device,
                                      warm_start=True) for m in modes}
            try:
                if wl == "mixed":    # alternate whole batches, 50/50
                    for i, lo in enumerate(range(0, sources.shape[0],
                                                 STORE_BATCH)):
                        sl = slice(lo, lo + STORE_BATCH)
                        if i % 2 == 0:
                            servers["ssd"].serve_stream(sources[sl])
                        else:
                            servers["p2p"].serve_stream(pairs[sl])
                elif wl == "p2p":
                    servers["p2p"].serve_stream(pairs)
                else:
                    servers["ssd"].serve_stream(sources)
            finally:
                engine.close()
            sts = [s.stats for s in servers.values()]
            requests = sum(s.requests for s in sts)
            busy = sum(s.busy_seconds for s in sts)
            hits = sum(s.page_hits for s in sts)
            misses = sum(s.page_misses for s in sts)
            real = sum(s.store_bytes_read for s in sts)
            modeled = sum(s.modeled_scan_bytes * s.stats.batches
                          for s in servers.values())
            row = {
                "workload": wl, "requests": requests,
                "cache_frac": 0.25, "policy": "2q",
                "hit_rate": hits / max(hits + misses, 1),
                "real_bytes": real,
                "filled_bytes": sum(s.store_bytes_filled for s in sts),
                "modeled_bytes": modeled,
                "queries_per_s": requests / busy if busy else 0.0,
            }
            rows.append(row)
            print(fmt_row([wl, f"{row['hit_rate']:.1%}",
                           f"{real/1e6:.2f}", f"{modeled/1e6:.2f}",
                           f"{row['queries_per_s']:.0f}"]))

        # Cold single-query footprint: the per-sweep guarantee behind
        # the p2p mode, measured with caching disabled so byte deltas
        # are exact sweep footprints.
        from repro.core.index import node_levels

        def cold_query_bytes(mode: str) -> int:
            store = IndexStore(store_dir, cache=PageCache(0))
            engine = StreamingQueryEngine(store, prefetch=False)
            try:
                lvl = node_levels(ix, np.arange(ix.n))[ix.perm]
                mid = np.nonzero((lvl > 0) & (lvl < ix.n_levels))[0]
                s = mid[:1].astype(np.int32)
                t = mid[-1:].astype(np.int32)
                dev = store.device.stats
                base = dev.bytes_seq + dev.bytes_rand
                engine.p2p(s, t) if mode == "p2p" else engine.ssd(s)
                return dev.bytes_seq + dev.bytes_rand - base
            finally:
                engine.close()

        cold = {"ssd": cold_query_bytes("ssd"),
                "p2p": cold_query_bytes("p2p")}
        cold["mixed"] = (cold["ssd"] + cold["p2p"]) // 2
        for row in rows:
            row["cold_query_bytes"] = cold[row["workload"]]
        print(f"cold single-query sweep: p2p {cold['p2p']/1e3:.0f} KB vs "
              f"ssd {cold['ssd']/1e3:.0f} KB")
        assert 0 < cold["p2p"] < cold["ssd"], (
            "cold p2p sweep did not read fewer bytes than a cold full "
            f"sweep: {cold['p2p']} vs {cold['ssd']}")
    return rows


def latency_sweep(ix, sources: np.ndarray, *,
                  modes=LATENCY_MODES) -> list:
    """ISSUE-8: per-mode latency percentiles + the tracing-overhead
    contract, from one 25% 2q raw store at queue depth 4.

    Each mode serves the same request stream twice — once under a
    :class:`~repro.obs.trace.Tracer`, once without.  The traced run
    must be *observation only*: answers and the page-cache counter
    totals are asserted bit-identical, the emitted Chrome trace must
    validate (balanced B/E, monotonic ts per tid) and contain the
    span taxonomy's required names.  Overhead is asserted on warm
    repeats: min-of-N traced engine-busy time within
    ``TRACE_OVERHEAD_FRAC`` (+ absolute slack) of untraced.  The
    emitted rows carry the untraced run's p50/p95/p99 from the
    server's fixed-bucket latency histogram — the numbers
    ``check_regression.py`` gates (``--latency-tol``)."""
    from repro.obs import Tracer, validate_chrome_trace

    rng = np.random.default_rng(2)
    targets = rng.integers(0, ix.n, size=sources.shape[0]).astype(np.int32)
    pairs = np.stack([sources, targets], axis=1)
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        store_dir = os.path.join(tmp, "store")
        ix.save_store(store_dir)
        budget = int(QD_FRAC * segment_bytes(store_dir))
        print(f"\n-- per-mode latency, traced vs untraced: "
              f"{sources.shape[0]} requests each from a "
              f"{QD_FRAC:.0%} 2q store, batch={STORE_BATCH}, "
              f"depth 4 --")
        print(fmt_row(["mode", "p50 ms", "p95 ms", "p99 ms",
                       "queries/s", "trace overhead"]))
        for mode in modes:
            reqs = pairs if mode == "p2p" else sources
            tracer = Tracer()

            def make(tr):
                return QueryServer(store_path=store_dir,
                                   cache_bytes=budget,
                                   batch_size=STORE_BATCH,
                                   cache_entries=0, cache_policy="2q",
                                   queue_depth=4, warm_start=True,
                                   mode=mode, tracer=tr)

            def counters(server):
                cs = server.store.cache.stats
                return (cs.hits, cs.misses, cs.bytes_read,
                        cs.bytes_filled, cs.evictions)

            straced, splain = make(tracer), make(None)
            try:
                r1 = straced.serve_stream(reqs)
                c1 = counters(straced)
                r0 = splain.serve_stream(reqs)
                c0 = counters(splain)
                a1 = np.stack([np.atleast_1d(r.dist) for r in r1])
                a0 = np.stack([np.atleast_1d(r.dist) for r in r0])
                assert np.array_equal(a1, a0), (
                    f"{mode}: traced answers diverged from untraced")
                assert c1 == c0, (
                    f"{mode}: traced cache counters diverged: "
                    f"{c1} vs {c0}")
                doc = tracer.chrome()
                problems = validate_chrome_trace(doc)
                assert not problems, problems[:5]
                names = {e["name"] for e in doc["traceEvents"]}
                need = {f"query.{mode}", "engine.dispatch", "level.read"}
                if mode == "ssd":
                    need |= {"pipe.submit", "level.wait",
                             "level.relax", "level.decode"}
                missing = need - names
                assert not missing, (
                    f"{mode}: trace missing spans {missing}")

                hist = splain.metrics.histogram(f"latency_ms.{mode}")
                s = hist.summary()
                qps = splain.stats.throughput()
                print(splain.stats.report(label=mode,
                                          batch_size=STORE_BATCH,
                                          latency=hist))

                # Overhead contract on warm repeats (min-of-N),
                # interleaved so machine-load drift lands on both
                # sides equally; the already-exported trace buffer is
                # cleared before each traced repeat so the contract
                # measures per-event cost, not the allocator/GC
                # pressure of a never-drained buffer.
                def one_busy(server):
                    b0 = server.stats.busy_seconds
                    server.serve_stream(reqs)
                    return server.stats.busy_seconds - b0

                plain_b = traced_b = float("inf")
                for _ in range(OVERHEAD_REPEATS):
                    plain_b = min(plain_b, one_busy(splain))
                    tracer.clear()
                    traced_b = min(traced_b, one_busy(straced))
                assert traced_b <= (plain_b * (1 + TRACE_OVERHEAD_FRAC)
                                    + TRACE_OVERHEAD_SLACK_S), (
                    f"{mode}: traced busy {traced_b:.4f}s exceeds "
                    f"untraced {plain_b:.4f}s by more than "
                    f"{TRACE_OVERHEAD_FRAC:.0%} + "
                    f"{TRACE_OVERHEAD_SLACK_S * 1e3:.0f} ms")
                overhead = traced_b / plain_b - 1 if plain_b else 0.0
            finally:
                straced.close()
                splain.close()
            row = {"mode": mode, "requests": int(s["count"]),
                   "mean_ms": s["mean"], "p50_ms": s["p50"],
                   "p95_ms": s["p95"], "p99_ms": s["p99"],
                   "queries_per_s": qps,
                   "trace_overhead_frac": overhead}
            rows.append(row)
            print(fmt_row([mode, f"{s['p50']:.2f}", f"{s['p95']:.2f}",
                           f"{s['p99']:.2f}", f"{qps:.0f}",
                           f"{overhead:+.1%}"]))
    return rows


def _timing_retry(fn, label: str, attempts: int = 3):
    """Run a sweep whose acceptance checks include *wall-clock*
    invariants (p99 orderings, cross-run q/s agreement) that a loaded
    CI machine can flake: retry on ``AssertionError`` up to
    ``attempts`` times and, if every attempt fails, re-raise with ALL
    failure messages — so a real regression shows up as the same
    message three times, while scheduler jitter shows as three
    different ones.  A deterministic divergence (bit-identity checks)
    fails every attempt identically."""
    failures = []
    for i in range(attempts):
        try:
            return fn()
        except AssertionError as exc:
            failures.append(f"attempt {i + 1}/{attempts}: {exc}")
            print(f"{label}: timing invariant failed "
                  f"({'retrying' if i + 1 < attempts else 'giving up'})"
                  f": {exc}")
    raise AssertionError(
        f"{label}: all {attempts} attempts failed --\n  "
        + "\n  ".join(failures))


def slo_sweep(engine, ix, slo_cfg: Config) -> list:
    """ISSUE-9: the mixed-traffic scheduler table, under
    :func:`_timing_retry` (the single-retry version of this still
    flaked CI under load).  The recorded rows are additionally gated —
    with configurable tolerances — by ``check_regression.py``."""
    return _timing_retry(lambda: _slo_sweep_once(engine, ix, slo_cfg),
                         label="slo sweep")


def _slo_sweep_once(engine, ix, slo_cfg: Config) -> list:
    """One mixed-traffic scheduler sweep — one server, two admission
    policies, one offered load.

    A seeded mixed ssd+p2p stream (shares, pool, rate, and SLO classes
    all from the ``bench.slo`` config section) is replayed twice with
    *identical* Poisson arrival gaps: once under ``scheduler="fifo"``
    (single shared queue, one ``max_wait_ms`` — the coalescing
    baseline) and once under ``scheduler="slo"`` (per-class queues,
    deadline-aware flushing).  Both servers carry the same SLO classes
    so deadline misses are counted against identical budgets.

    In-bench acceptance (also re-checked baseline-free by
    ``check_regression.py``):

    * every answered request is bit-identical to the unscheduled path
      (singleton engine calls) under BOTH policies;
    * the cheap class's (p2p) p99 under ``slo`` is strictly below the
      fifo baseline's;
    * wall-clock throughput matches across policies within
      ``SLO_QPS_TOL`` — the p99 win must come from scheduling, not
      from answering less traffic."""
    n = int(slo_cfg.get("requests", 256))
    rate = float(slo_cfg.get("rate", 250.0))
    batch = int(slo_cfg.get("batch", 16))
    max_wait = float(slo_cfg.get("max_wait_ms", 60.0))
    pool = int(slo_cfg.get("p2p_pool", 16))
    mix = slo_cfg.get("mix", {"ssd": 1, "p2p": 3})
    classes = slo_cfg.get("classes", {})
    modes = tuple(sorted(mix))

    rng = np.random.default_rng(7)
    stream_cfg = Config(None, defaults={"serve": {"mix": mix}})
    stream = mixed_request_stream(stream_cfg, ix.n, n, rng,
                                  p2p_pool=pool)
    gaps = rng.exponential(1.0 / rate, n).tolist()

    # The unscheduled path: one singleton engine call per distinct
    # request — what every scheduled answer must be bit-identical to.
    oracle = {}
    for mode, args in stream:
        if (mode, args) in oracle:
            continue
        if mode == "p2p":
            s = np.asarray([args[0]], dtype=np.int32)
            t = np.asarray([args[1]], dtype=np.int32)
            oracle[(mode, args)] = np.float32(engine.p2p(s, t)[0])
        else:
            s = np.asarray([args[0]], dtype=np.int32)
            oracle[(mode, args)] = engine.ssd(s)[0]

    print(f"\n-- mixed-traffic SLO scheduler: {n} requests at "
          f"{rate:.0f}/s, mix {mix}, batch={batch}, fifo "
          f"max_wait={max_wait:g} ms --")
    print(fmt_row(["policy", "class", "requests", "p50 ms", "p99 ms",
                   "misses", "wall q/s"]))
    rows, p99 = [], {}
    for policy in ("fifo", "slo"):
        server = QueryServer(engine, batch_size=batch,
                             max_wait_ms=max_wait,
                             cache_entries=4096, mode=modes[0],
                             modes=modes, scheduler=policy,
                             slo=classes)
        server.warmup()

        async def drive():
            tasks = []
            for (mode, args), gap in zip(stream, gaps):
                tasks.append(asyncio.create_task(
                    server.submit(*args, mode=mode)))
                await asyncio.sleep(gap)
            await server.drain()
            return await asyncio.gather(*tasks)

        t0 = time.perf_counter()
        results = asyncio.run(drive())
        wall = time.perf_counter() - t0
        qps = n / wall

        for (mode, args), r in zip(stream, results):
            want = oracle[(mode, args)]
            assert np.array_equal(np.asarray(r.dist),
                                  np.asarray(want)), (
                f"{policy}: {mode}{args} diverged from the "
                f"unscheduled path")
        for row in server.slo_report():
            row = dict(row, policy=policy,
                       queries_per_s=qps,
                       miss_rate=(row["deadline_misses"]
                                  / max(row["requests"], 1)),
                       cheap=row["mode"] == "p2p")
            rows.append(row)
            p99[(row["cls"], policy)] = row["p99_ms"]
            print(fmt_row([
                policy, row["cls"], row["requests"],
                f"{row['p50_ms']:.2f}", f"{row['p99_ms']:.2f}",
                row["deadline_misses"], f"{qps:.0f}"]))
        p99[("__qps__", policy)] = qps

    qf, qs = p99[("__qps__", "fifo")], p99[("__qps__", "slo")]
    assert abs(qs - qf) / qf <= SLO_QPS_TOL, (
        f"slo wall throughput {qs:.0f} q/s strayed more than "
        f"{SLO_QPS_TOL:.0%} from fifo's {qf:.0f}")
    assert p99[("p2p", "slo")] < p99[("p2p", "fifo")], (
        f"cheap-class p99 under slo ({p99[('p2p', 'slo')]:.2f} ms) "
        f"not strictly below the fifo baseline "
        f"({p99[('p2p', 'fifo')]:.2f} ms)")
    return rows


def run(dataset: str = "USRN-like", config_path: str | None = None
        ) -> dict:
    cfg = load_bench_config(config_path)
    if cfg.path:
        print(f"bench grid: {cfg.path}")
    g = dataset_suite()[dataset]
    art = build_hod_cached(dataset, g)
    rng = np.random.default_rng(0)
    # distinct sources: measure the sweeps, not the LRU cache
    n_requests = int(cfg.get("bench.n_requests"))
    sources = rng.choice(g.n, size=min(n_requests, g.n),
                         replace=False).astype(np.int32)

    print(f"\n== Serving throughput ({dataset}: n={g.n} m={g.m}, "
          f"{sources.shape[0]} requests) ==")
    print(fmt_row(["batch", "queries/s", "ms/query", "io ms/query",
                   "io ms/batch", "seq blocks"]))
    serve_rows = []
    for b in cfg.get("bench.batch_sizes"):
        server = QueryServer(art.engine, batch_size=b, cache_entries=0)
        server.warmup()
        results = server.serve_stream(sources)
        st = server.stats
        io = server.modeled_io()
        io_s = io.modeled_seconds()
        qps = st.throughput()
        print(fmt_row([
            b, f"{qps:.0f}", f"{1e3/qps:.2f}" if qps else "-",
            f"{io_s/st.requests*1e3:.2f}",
            f"{io_s/st.batches*1e3:.1f}", io.seq_blocks]))
        assert all(np.isfinite(r.dist[: g.n]).all() for r in results)
        serve_rows.append({
            "batch": b, "queries_per_s": qps,
            "io_seconds_per_query": io_s / st.requests,
            "io_seconds_per_batch": io_s / st.batches,
            "seq_blocks": io.seq_blocks,
        })

    nstore = int(cfg.get("bench.store.requests"))
    store_srcs = sources[: min(nstore, sources.shape[0])]
    store_rows = store_cache_sweep(
        art.index, store_srcs,
        cache_grid=[tuple(fp) for fp in
                    cfg.get("bench.store.cache_grid")],
        codecs=tuple(cfg.get("bench.store.codecs")),
        codec_fracs=tuple(cfg.get("bench.store.codec_fracs")))
    workload_rows = workload_mix_sweep(art.index, store_srcs)
    # both sweeps assert on wall-clock-derived quantities (modeled
    # stall folds measured decode times; trace overhead is a busy-time
    # ratio), so they get the same retry protection as slo/fleet
    qd_rows = _timing_retry(lambda: queue_depth_sweep(
        art.index, store_srcs,
        depths=tuple(cfg.get("bench.queue_depth.depths")),
        codecs=tuple(cfg.get("bench.queue_depth.codecs"))),
        label="queue-depth sweep")
    latency_rows = _timing_retry(lambda: latency_sweep(
        art.index, store_srcs,
        modes=tuple(cfg.get("bench.latency.modes"))),
        label="latency sweep")
    nfleet = int(cfg.get("bench.fleet.requests"))
    fleet_rows = fleet_sweep(art.index,
                             sources[: min(nfleet, sources.shape[0])],
                             cfg.sub("bench.fleet"))
    slo_rows = slo_sweep(art.engine, art.index, cfg.sub("bench.slo"))

    cold = cold_start_latency(art.index)
    print(f"cold start (batch={COLD_BATCH}): index load "
          f"{cold['load_s']*1e3:.0f} ms, +warm-start compile "
          f"{cold['warm_s']*1e3:.0f} ms, load->first-response "
          f"{cold['first_s']*1e3:.0f} ms")
    return {"serve": serve_rows, "store": store_rows,
            "workloads": workload_rows, "queue_depth": qd_rows,
            "latency": latency_rows, "fleet": fleet_rows,
            "slo": slo_rows, "cold_start": [cold]}


if __name__ == "__main__":
    run()
