"""Store-backed streaming query execution (DESIGN.md §6).

:class:`StreamingQueryEngine` answers the same batched SSD/SSSP queries
as :class:`~repro.core.query.QueryEngine` but never materializes a
whole :class:`~repro.core.index.SweepPlan`: each sweep walks its
segment file level by level, pulling one ``[M_pad, K_fix]`` slab at a
time through the store's page cache and feeding it to a jitted,
state-donating level step (`QueryEngine._run_plan_stream`).  Peak plan
memory is therefore O(largest level), not O(index), and the
``IOStats`` on the store's :class:`~repro.core.io_sim.BlockDevice`
record the *actual* block reads the query caused (cache misses), not a
synthetic charge.

Answers are bit-identical to the in-memory engine: the level bodies are
the same methods, applied to the same slab values in the same order —
``lax.scan`` over resident levels and a Python loop over streamed
levels compose identical (min, +)/max scatters.  SSSP reconstruction
walks the plans in the order ``plan_b → plan_core → plan_f`` (the
reverse of the distance pass, for cache reuse); the per-plan
max-merges commute, so predecessors stay bit-identical to the
in-memory executor's ``f → core → b`` order (asserted in
tests/test_storage.py).

**Recon pinning** (ROADMAP "recon reuse"; DESIGN.md §6): an SSSP query
re-reads every distance-pass block during reconstruction, so the
distance sweeps pin the levels they stream (``PageCache`` pin leases,
bounded by the pin budget) and reconstruction unpins each level right
after consuming it.  ``plan_b`` is re-read first and is usually still
warm even unpinned; ``plan_f`` — touched a whole sweep earlier, i.e.
exactly the blocks a cyclic-thrash policy would have dropped — is the
one the pins save.  A ``finally`` ledger releases any leftover leases
even when a sweep raises.

``prefetch=True`` streams each plan through the depth-N async
:class:`~repro.storage.pipeline.ReadPipeline`: up to ``queue_depth``
levels' block reads stay in flight (ordered submit/reap on a dedicated
io thread, batched extent preads) and codec decompress-on-fill runs on
a ``decode_workers``-wide pool, so neither the read nor the decode
ever blocks the query thread's jit step.  All cache-state transitions
still happen on the query thread in block order
(``PageCache.begin_fill``), so hit/miss/eviction/byte sequences — and
therefore answers — are bit-identical to the synchronous
``prefetch=False`` path at every depth.  Fill failures (e.g. a CRC
mismatch on a corrupt segment) always surface in the querying thread:
the level generator re-raises them on reap, and if the consumer
abandons the sweep mid-stream the generator's cleanup drains every
in-flight fill so no error is silently swallowed and no placeholder is
left incomplete.  Bounded sweeps (P2P, threshold, kNN, top-k) bypass
the pipeline and read synchronously, so a skipped level provably skips
the device I/O, not just the compute.
"""
from __future__ import annotations

import os
from collections import deque
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import shardlib as sl
from ..core.index import node_levels
from ..core.query import INF, QueryEngine, _knn_select
from ..obs.trace import span_if
from .blockfile import IndexStore
from .pipeline import PipelineStats, ReadPipeline

__all__ = ["StreamingQueryEngine"]


class StreamingQueryEngine(QueryEngine):
    """Batched SSD/SSSP over an :class:`IndexStore`, one level slab at a
    time.

    Supports ``core_mode`` ``"closure"`` and ``"bellman"`` (the jitted
    core searches over the resident tier) and ``"dijkstra"`` (host heap
    over the resident core CSR).  The resident tier — permutations,
    core closure/CSR — stays in memory; the three plan segments stream.
    """

    def __init__(self, store: IndexStore, core_mode: str = "closure",
                 use_pallas: bool = False, eps: float = 0.0,
                 interpret: Optional[bool] = None, prefetch: bool = True,
                 queue_depth: int = 4, decode_workers: int = 2,
                 tracer=None):
        self.store = store
        #: the ServingFleet when the store is sharded (repro/fleet) —
        #: surfaced so servers can report per-shard stats without
        #: reaching through storage internals.
        self.fleet = getattr(store, "fleet", None)
        self.prefetch = bool(prefetch)
        self._init_engine(store.resident, core_mode, use_pallas, eps,
                          interpret)
        self._core_jit = jax.jit(
            lambda dist, core: self._core_update(dist, core, self.core_mode))
        # Level steps: state (arg 0) is donated, so the sweep runs with
        # one live state buffer + one level slab.  assoc is an operand
        # of both steps (unused by relax) so they share a signature.
        self._relax_step = jax.jit(
            lambda dist, dst, src, w, assoc, valid:
            self._relax_level(dist, dst, src, w, assoc, valid),
            donate_argnums=0)
        self._recon_step = jax.jit(
            lambda pred, dist, dst, src, w, assoc, valid:
            self._recon_level(pred, dist, dst, src, w, assoc, valid),
            donate_argnums=0)
        # Query-mode steps (DESIGN.md §7).  Same O(1)-trace discipline:
        # each jits once per slab shape; the threshold ``d`` and the
        # range/cut bounds are *operands*, not closure constants, so a
        # new query never re-traces.
        self._relax_rev_step = jax.jit(
            lambda dlab, dst, src, w, assoc, valid:
            self._relax_level_rev(dlab, dst, src, w, assoc, valid),
            donate_argnums=0)
        self._thresh_step = jax.jit(
            lambda dist, d, dst, src, w, assoc, valid: jnp.where(
                (r := self._relax_level(dist, dst, src, w, assoc,
                                        valid)) <= d, r, INF),
            donate_argnums=0)
        self._meet_min = jax.jit(
            lambda fwd, bwd: jnp.min(fwd + bwd, axis=1))
        self._suffix_min = jax.jit(
            lambda fwd, cut: jnp.min(jnp.where(
                jnp.arange(fwd.shape[1])[None, :] >= cut, fwd, INF),
                axis=1))
        self._range_live = jax.jit(
            lambda dist, lo, hi: jnp.any(jnp.isfinite(dist) & (
                jnp.arange(dist.shape[1])[None, :] >= lo) & (
                jnp.arange(dist.shape[1])[None, :] < hi)))
        self._clamp_step = jax.jit(
            lambda dist, d: jnp.where(dist <= d, dist, INF),
            donate_argnums=0)
        self._pipe = (ReadPipeline(store, queue_depth=queue_depth,
                                   decode_workers=decode_workers)
                      if self.prefetch else None)
        if tracer is not None:
            self.set_tracer(tracer)

    # --------------------------------------------------------- observability
    def set_tracer(self, tracer) -> None:
        """Attach a :class:`repro.obs.trace.Tracer` (DESIGN.md §11) to
        every layer this engine drives: relax spans (``QueryEngine``
        hook), pipeline submit/read/decode/wait spans, cache
        hit/miss/evict instants (``PageCache.on_event``, routed to the
        synthetic ``submit`` track so the query thread's own span
        sequence stays depth-invariant), and modeled-device access
        instants (``BlockDevice.on_access``, ``device`` track).  Pass
        ``None`` to detach everything."""
        self.tracer = tracer
        self._seg_short: dict = {}   # cache-namespace -> short label
        if self._pipe is not None:
            self._pipe.tracer = tracer
        self.store.cache.on_event = (self._on_cache_event
                                     if tracer is not None else None)
        self.store.device.on_access = (self._on_device_access
                                       if tracer is not None else None)

    def _on_cache_event(self, kind: str, key, nbytes: int) -> None:
        tr = self.tracer
        if tr is None:
            return
        if isinstance(key, tuple) and len(key) == 2:
            ns, block = key
            seg = self._seg_short.get(ns)
            if seg is None:   # memoized: this fires per block touch
                seg = self._seg_short[ns] = os.path.basename(str(ns))
            block = int(block)
        else:
            seg, block = str(key), -1
        tr.instant(f"cache.{kind}", track="submit", seg=seg,
                   block=block, bytes=int(nbytes))

    def _on_device_access(self, block_id: int, nbytes: int,
                          seq: bool) -> None:
        tr = self.tracer
        if tr is not None:
            tr.instant("device.read", track="device",
                       block=int(block_id), bytes=int(nbytes),
                       seq=bool(seq))

    def pipeline_stats(self) -> Optional[PipelineStats]:
        """The live :class:`PipelineStats` (overlap/stall metrics), or
        ``None`` when running synchronously (``prefetch=False``)."""
        return self._pipe.stats if self._pipe is not None else None

    # ------------------------------------------------------------- streaming
    def _levels(self, name: str, pin: bool = False,
                unpin_after: bool = False) -> Iterator[tuple]:
        """Yield one plan's level slabs in scan order.

        ``pin=True`` takes a pin lease on every block read (the
        distance pass of an SSSP query); ``unpin_after=True`` releases
        a level's leases right after the consumer finishes with it
        (the reconstruction pass).  With the pipeline, up to
        ``queue_depth`` levels stay in flight: each reap tops the
        window back up before waiting, and reaping re-raises fill
        errors in the querying thread.  The ``finally`` drains every
        in-flight ticket when the consumer abandons the sweep, so a
        failed fill can never be silently lost and no placeholder is
        left incomplete.
        """
        n = self.store.n_real(name)
        if self._pipe is None:
            for lvl in range(n):
                with span_if(self.tracer, "level.read", plan=name,
                             level=lvl):
                    slab = self.store.read_level(name, lvl, pin=pin)
                yield slab
                if unpin_after:
                    self.store.unpin_level(name, lvl)
            return
        pipe = self._pipe
        pipe.begin_sweep()
        tickets: "deque" = deque()
        nxt = 0

        def top_up():
            nonlocal nxt
            while nxt < n and len(tickets) < pipe.queue_depth:
                tickets.append(pipe.submit_level(name, nxt, pin=pin))
                nxt += 1

        try:
            top_up()
            for lvl in range(n):
                ticket = tickets.popleft()
                top_up()
                yield pipe.reap(ticket)
                if unpin_after:
                    self.store.unpin_level(name, lvl)
        finally:
            pipe.drain(tickets)

    def _sweep(self, state: jnp.ndarray, name: str, step,
               pin: bool = False) -> jnp.ndarray:
        return self._run_plan_stream(state, self._levels(name, pin=pin),
                                     step, label=name)

    def _init_dist(self, sources_perm: np.ndarray) -> jnp.ndarray:
        s = sources_perm.shape[0]
        dist = jnp.full((s, self.index.n_pad), INF, jnp.float32)
        dist = dist.at[jnp.arange(s), jnp.asarray(sources_perm)].set(0.0)
        return sl.shard(dist, "batch", None)

    def _apply_core(self, dist: jnp.ndarray) -> jnp.ndarray:
        if not self.index.n_core:
            return dist
        with span_if(self.tracer, "core.search", mode=self.core_mode):
            if self.core_mode == "dijkstra":
                # Paper-faithful host heap over the resident core CSR —
                # the same shared helper the in-memory validation mode
                # uses (QueryEngine._core_dijkstra_host).
                return jnp.asarray(
                    self._core_dijkstra_host(np.array(dist)))
            return self._core_jit(dist, self._core)

    def _ssd_stream(self, sources_perm: np.ndarray,
                    pin: bool = False) -> jnp.ndarray:
        dist = self._init_dist(sources_perm)
        dist = self._sweep(dist, "plan_f", self._relax_step, pin=pin)
        dist = self._apply_core(dist)
        return self._sweep(dist, "plan_b", self._relax_step, pin=pin)

    def _unpin_plan(self, name: str) -> None:
        """Release every pin lease a distance sweep may still hold on
        one plan's levels (idempotent; sticky segment pins unaffected)."""
        for lvl in range(self.store.n_real(name)):
            self.store.unpin_level(name, lvl)

    # ---------------------------------------------------------------- public
    # Each call runs in the engine's three phases (QueryEngine._served):
    # here the whole streamed sweep is the dispatch, and no count comes
    # back.
    def ssd(self, sources: np.ndarray) -> np.ndarray:
        sources = np.asarray(sources, dtype=np.int32)
        return self._served(
            "ssd", len(sources),
            lambda: (self._ssd_stream(self.index.perm[sources]), None),
            self._unpermute)

    def sssp(self, sources: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        sources = np.asarray(sources, dtype=np.int32)
        return self._served(
            "sssp", len(sources), lambda: (self._sssp_stream(sources), None),
            lambda out: tuple(self._unpermute(x) for x in out))

    def _sssp_stream(self, sources: np.ndarray):
        try:
            # Distance pass pins the levels it streams: reconstruction
            # re-reads all of them immediately after (recon reuse).
            dist = self._ssd_stream(self.index.perm[sources], pin=True)
            pred = jnp.full((dist.shape[0], self.index.n_pad), -1,
                            jnp.int32)
            # Reverse plan order for cache affinity: plan_b was streamed
            # moments ago, plan_f a whole sweep ago (the pinned one).
            # The per-plan scatter-maxes commute, so pred is
            # bit-identical to the in-memory f -> core -> b order.
            for name in ("plan_b", "plan_core", "plan_f"):
                pred = self._run_plan_stream(
                    pred, self._levels(name, unpin_after=True),
                    lambda p, *slab: self._recon_step(p, dist, *slab),
                    label=name)
        finally:
            for name in ("plan_f", "plan_b"):
                self._unpin_plan(name)
        return dist, pred

    # -------------------------------------------- bounded sweeps (§7)
    def _read(self, name: str, lvl: int):
        """One level slab, read synchronously (bounded sweeps bypass the
        prefetch thread so a skip / early exit provably skips the I/O,
        not just the compute)."""
        with span_if(self.tracer, "level.read", plan=name, level=lvl):
            return tuple(jnp.asarray(a)
                         for a in self.store.read_level(name, lvl))

    def p2p(self, sources: np.ndarray, targets: np.ndarray,
            early_term: bool = True) -> np.ndarray:
        """Point-to-point distances (:meth:`_p2p_stream`)."""
        return self._served(
            "p2p", len(sources),
            lambda: (self._p2p_stream(sources, targets, early_term), None),
            np.asarray)

    def _p2p_stream(self, sources: np.ndarray, targets: np.ndarray,
                    early_term: bool):
        """Point-to-point distances ``dist(sources[i], targets[i])`` by
        meet-in-the-middle (DESIGN.md §7), reading strictly less than a
        full SSD sweep:

        * the forward half skips every ``plan_f`` level below the
          lowest source level (labels there are provably still +inf);
        * the backward-label half walks ``plan_b`` in *reverse* scan
          order (ascending rank), skips its tail below the lowest
          target level, and — with ``early_term`` — stops as soon as
          every row's best meeting distance is <= the suffix-min of its
          (final) forward labels over the ids future levels can still
          touch: backward labels are nonnegative, so no later meet can
          beat the bound.  ``early_term=False`` reads every kept level;
          answers are bit-identical either way.
        """
        sources = np.asarray(sources, dtype=np.int32)
        targets = np.asarray(targets, dtype=np.int32)
        ix = self.index
        src_perm = ix.perm[sources]
        tgt_perm = ix.perm[targets]
        lvl_s = int(node_levels(ix, src_perm).min())
        lvl_t = int(node_levels(ix, tgt_perm).min())

        fwd = self._init_dist(src_perm)
        start_f = int(np.searchsorted(self._level_ids_f, lvl_s,
                                      side="left"))
        for lvl in range(start_f, self.store.n_real("plan_f")):
            fwd = self._relax_step(fwd, *self._read("plan_f", lvl))
        fwd = self._apply_core(fwd)

        bwd = self._init_dist(tgt_perm)
        best = self._meet_min(fwd, bwd)
        keep = np.nonzero(self._level_ids_b >= lvl_t)[0]
        for j in (range(int(keep.max()), -1, -1) if keep.size else ()):
            bwd = self._relax_rev_step(bwd, *self._read("plan_b", j))
            best = self._meet_min(fwd, bwd)
            if early_term and j > 0:
                cut = int(ix.level_ptr[int(self._level_ids_b[j - 1])])
                if bool(jnp.all(best <= self._suffix_min(fwd, cut))):
                    break
        return best

    def ssd_within(self, sources: np.ndarray, d: float) -> np.ndarray:
        """All distances ``<= d`` (:meth:`_within_stream`)."""
        return self._served(
            "within", len(sources),
            lambda: (self._within_stream(sources, d), None),
            self._unpermute)

    def _within_stream(self, sources: np.ndarray, d: float):
        """All distances ``<= d`` (rest ``+inf``), original node order.

        The threshold body clamps labels past ``d`` inside every level
        step, so a level whose *gather range* holds no finite label is
        provably inert — the sweep skips its reads entirely.  Forward
        level ``g`` gathers its own level's ids
        ``[level_ptr[g], level_ptr[g+1])``; backward level ``g``
        gathers strictly-higher ranks ``>= level_ptr[g+1]``.
        """
        sources = np.asarray(sources, dtype=np.int32)
        ix = self.index
        lp = ix.level_ptr
        d = jnp.float32(d)
        dist = self._init_dist(ix.perm[sources])
        dist = jnp.where(dist <= d, dist, INF)   # d < 0: nothing survives
        for lvl in range(self.store.n_real("plan_f")):
            g = int(self._level_ids_f[lvl])
            if not bool(self._range_live(dist, int(lp[g]),
                                         int(lp[g + 1]))):
                continue
            dist = self._thresh_step(dist, d, *self._read("plan_f", lvl))
        dist = self._apply_core(dist)
        dist = jnp.where(dist <= d, dist, INF)   # mask core output
        for lvl in range(self.store.n_real("plan_b")):
            g = int(self._level_ids_b[lvl])
            if not bool(self._range_live(dist, int(lp[g + 1]),
                                         dist.shape[1])):
                continue
            dist = self._thresh_step(dist, d, *self._read("plan_b", lvl))
        return dist

    def knn(self, sources: np.ndarray, k: int
            ) -> Tuple[np.ndarray, np.ndarray]:
        """The ``k`` nearest nodes of each source (DESIGN.md §7): a
        threshold sweep whose per-row radius *shrinks adaptively*.

        Before each level the radius is the row's kth-smallest current
        label — labels only decrease, so it is always an upper bound on
        the row's final kth distance, and clamping labels past it is
        sound by the same nonnegative-weight argument as
        :meth:`ssd_within` (a top-k node's true chain labels are all
        ``<=`` its final distance ``<=`` the radius, so they always
        survive; only overestimates are erased).  Levels whose gather
        range holds no live label are skipped — reads included, via the
        synchronous bypass.  Returns ``(nodes, dist)``, each ``[S, k]``
        in original node ids: ascending ``(distance, node id)`` with
        the source itself at distance 0; rows with fewer than ``k``
        reachable nodes pad with ``(-1, +inf)``.  Bit-identical to the
        in-memory :meth:`QueryEngine.knn` (full sweep + host top-k).
        """
        sources = np.asarray(sources, dtype=np.int32)
        ix = self.index
        if not 1 <= k <= ix.n:
            raise ValueError(f"k must be in [1, {ix.n}], got {k}")
        lp = ix.level_ptr
        dist = self._init_dist(ix.perm[sources])

        def radius(d):
            # per-row kth-smallest current label, as a [S, 1] operand
            # (broadcasts against [S, n_pad] inside the jitted steps)
            part = np.partition(np.asarray(d), k - 1, axis=1)
            return jnp.asarray(part[:, k - 1:k])

        for lvl in range(self.store.n_real("plan_f")):
            g = int(self._level_ids_f[lvl])
            r = radius(dist)
            dist = self._clamp_step(dist, r)
            if not bool(self._range_live(dist, int(lp[g]),
                                         int(lp[g + 1]))):
                continue
            dist = self._thresh_step(dist, r, *self._read("plan_f", lvl))
        dist = self._apply_core(dist)
        for lvl in range(self.store.n_real("plan_b")):
            g = int(self._level_ids_b[lvl])
            r = radius(dist)
            dist = self._clamp_step(dist, r)
            if not bool(self._range_live(dist, int(lp[g + 1]),
                                         dist.shape[1])):
                continue
            dist = self._thresh_step(dist, r, *self._read("plan_b", lvl))
        return _knn_select(np.asarray(dist)[:, ix.perm], k)

    def _far_slice(self, dist: jnp.ndarray, lo: int,
                   hi: int) -> np.ndarray:
        """Per-row farness contribution of perm-id columns [lo, hi) —
        summed on the host in float64 so integer-valued distances
        accumulate exactly (the top-k prune must never overshoot)."""
        d = np.asarray(dist[:, lo:hi])
        return np.where(np.isfinite(d), d, 0.0).sum(axis=1,
                                                    dtype=np.float64)

    def ssd_bounded(self, sources: np.ndarray, threshold: float
                    ) -> Tuple[Optional[np.ndarray], bool]:
        """SSD that may abandon mid-backward-sweep once every row's
        farness provably exceeds ``threshold`` (the top-k closeness
        prune, DESIGN.md §7).

        The backward sweep finalizes labels level by level descending:
        after the level at graph level ``g``, every id ``>=
        level_ptr[g]`` is final (later levels only scatter lower).  The
        running sum of finite finalized distances is therefore a lower
        bound on each row's farness; when it exceeds ``threshold`` for
        every row the remaining levels go unread.  Returns
        ``(dist_in_original_order, True)`` for a completed sweep —
        bit-identical to :meth:`ssd` — or ``(None, False)``.
        """
        sources = np.asarray(sources, dtype=np.int32)
        ix = self.index
        lp = ix.level_ptr
        dist = self._init_dist(ix.perm[sources])
        for lvl in range(self.store.n_real("plan_f")):
            dist = self._relax_step(dist, *self._read("plan_f", lvl))
        dist = self._apply_core(dist)
        nb = self.store.n_real("plan_b")
        if nb:
            cut = int(lp[int(self._level_ids_b[0]) + 1])
            far = self._far_slice(dist, cut, dist.shape[1])
            if np.all(far > threshold):
                return None, False
            for lvl in range(nb):
                dist = self._relax_step(dist, *self._read("plan_b", lvl))
                new_cut = int(lp[int(self._level_ids_b[lvl])])
                far += self._far_slice(dist, new_cut, cut)
                cut = new_cut
                if lvl + 1 < nb and np.all(far > threshold):
                    return None, False
        return np.asarray(dist)[:, ix.perm], True

    def close(self) -> None:
        if self._pipe is not None:
            self._pipe.close()
        self.store.close()
