"""HoD query processing (paper §5) as one compiled SweepPlan executor.

An SSD query runs three phases (paper §5): a *forward search* over ``G_f``,
a *core search* inside ``G_c``, and a *backward search* over ``G_b``.  The
paper's key property — traversal order equals file order, so every phase is
one sequential scan — maps onto TPU as ONE ``lax.scan`` over the levels of
a static-shape :class:`~repro.core.index.SweepPlan` (DESIGN.md §5):

* **forward**: plan levels ascend rank; every edge goes strictly up-rank
  and same-rank nodes are never adjacent, so each node's distance is final
  before its out-edges are relaxed (single-pass DAG sweep);
* **core**: one min-plus (tropical) matmul against the precomputed core
  closure (beyond-paper; the paper-faithful iterative/Dijkstra modes are
  kept for validation);
* **backward**: plan levels descend rank — the paper's heap-free linear
  scan, verbatim.

Every plan level is one fused bucketed relaxation (``relax_bucketed`` —
Pallas kernel or jnp fallback, selected per engine, same executor either
way).  Because the plan is padded to ``[L_pad, M_pad, K_fix]``, the scan
body traces ONCE per sweep: trace count is independent of the graph's
level count, and no per-level Python dispatch survives.

Queries are *batched over sources* (``dist`` is ``[S, n_pad]``): the
paper's flagship application (closeness estimation, Table 5) issues
hundreds of SSD queries, which here amortize into dense VPU work.

SSSP (paper §6) rides the SAME executor: after distances are final, each
plan (forward, core, backward) is re-scanned with the reconstruction
level-body — every augmented edge ``(u, v, w, assoc)`` with
``dist[u] + w == dist[v]`` scatters its predecessor annotation into
``pred[v]``.  The assoc slots live in the same plan buckets, so there is
no separate reconstruction layout.  Any matching edge yields a valid
shortest-path predecessor, so duplicate winners are harmless; correctness
follows from the arch-path argument (Theorem 1): the realizing path's
last edge is always tight.
"""
from __future__ import annotations

import functools
import heapq
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import shardlib as sl
from ..kernels.edge_relax.ops import relax_bucketed
from ..obs.trace import span_if
from .index import HoDIndex, SweepPlan, node_levels, plan_level_ids

__all__ = ["QueryEngine", "dijkstra_reference"]

INF = jnp.float32(jnp.inf)


def _knn_select(dist: np.ndarray, k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Host top-k over a ``[S, n]`` distance matrix (original node
    order): the k smallest entries per row, ascending by ``(distance,
    node id)``; unreachable tail padded with ``(-1, +inf)``.  Shared by
    the in-memory and streaming kNN modes so ties break identically."""
    s, n = dist.shape
    nodes = np.full((s, k), -1, np.int32)
    out = np.full((s, k), np.inf, np.float32)
    ids = np.arange(n)
    for i in range(s):
        order = np.lexsort((ids, dist[i]))[:k]
        d = dist[i, order]
        m = int(np.isfinite(d).sum())     # finite entries sort first
        nodes[i, :m] = order[:m]
        out[i, :m] = d[:m]
    return nodes, out


def _program(impl, core_mode: str):
    """``impl`` jitted with its core mode bound, under ``impl``'s own
    name: the host dispatch reads ``PjitFunction(<impl>)`` and the
    device program ``jit_<impl>``, not ``jit__unknown``."""
    fn = functools.partial(impl, core_mode=core_mode)
    fn.__name__ = impl.__name__
    return jax.jit(fn)


def _count_tags(counts: dict, fill: int) -> dict:
    """Span tags of a program's counts, under their own names: a scalar
    count as it is, a per-row ``[S]`` count summed over the batch's
    first ``fill`` rows (the rest are padding)."""
    return {name: int(v) if np.ndim(v) == 0 else int(np.sum(v[:fill]))
            for name, v in counts.items()}


def _plan_to_device(plan: SweepPlan):
    """Device-resident plan arrays, in the executor's scan order."""
    return (jnp.asarray(plan.dst), jnp.asarray(plan.src_idx),
            jnp.asarray(plan.w), jnp.asarray(plan.assoc),
            jnp.asarray(plan.row_valid), jnp.asarray(plan.level_mask))


class _Plans(NamedTuple):
    """The three device-resident sweep plans.  Every jitted query takes
    them as an *operand*, never as a closed-over constant: a constant
    would be embedded in the compiled program, whose size (and compile
    time) would then grow with the index."""
    f: tuple   # forward search (§5.1)
    b: tuple   # backward search (§5.3)
    c: tuple   # core edges, read only by SSSP reconstruction (§6)
    perm: jnp.ndarray   # [n] original id -> permuted id (SSD answers)


def _dense_core_adjacency(ix: HoDIndex) -> np.ndarray:
    """Dense [C, C] core adjacency from the raw CSR (scatter, no Python
    loop) — only the paper-faithful Bellman core mode reads it."""
    c = ix.n_core
    adj = np.full((c, c), np.inf, dtype=np.float32)
    if c:
        np.fill_diagonal(adj, 0.0)
        if ix.core_dst.shape[0]:
            cu = np.repeat(np.arange(c, dtype=np.int32),
                           np.diff(ix.core_ptr))
            np.minimum.at(adj, (cu, ix.core_dst),
                          ix.core_w.astype(np.float32))
    return adj


def _per_batch_shard(fn, batched: Tuple[bool, ...], *args):
    """``fn(*args)`` run once per shard of the logical ``"batch"`` axis
    under an active mesh (a plain call otherwise).  XLA cannot partition
    a Pallas TPU kernel on its own, so the sweep's kernels see their
    per-device rows through ``shard_map``.  ``batched[i]`` says whether
    ``args[i]`` leads with the batch axis (else it is replicated); the
    result does."""
    b = sl.logical_to_spec("batch")
    return sl.maybe_shard_map(
        fn, in_specs=tuple(b if x else P() for x in batched),
        out_specs=b)(*args)


def _block_rows(b: jnp.ndarray, block_k: int = 256) -> jnp.ndarray:
    """``b`` [K, N] as the ``[kb, block_k, N]`` k blocks that
    :func:`_minplus_blocks` consumes, padded with ``+inf`` rows (which
    never win a minimum) to a whole number of blocks.  A function of
    ``b`` alone: a loop that multiplies by a constant ``b`` blocks it
    once, outside the loop, since XLA does not hoist the pad itself."""
    k_dim, n_dim = b.shape
    b = jnp.pad(b, ((0, (-k_dim) % block_k), (0, 0)),
                constant_values=jnp.inf)
    return b.reshape(-1, block_k, n_dim)


def _minplus_blocks(a: jnp.ndarray, b_blocks: jnp.ndarray) -> jnp.ndarray:
    """out[s, j] = min_k a[s, k] + b[k, j] over ``b`` already blocked by
    :func:`_block_rows`, accumulated block by block; only the small
    ``[S, K]`` ``a`` is padded here."""
    kb, block_k, n_dim = b_blocks.shape
    s_dim, k_dim = a.shape
    a = jnp.pad(a, ((0, 0), (0, kb * block_k - k_dim)),
                constant_values=jnp.inf)
    a_blocks = a.reshape(s_dim, kb, block_k).transpose(1, 0, 2)

    def body(acc, blk):
        ab, bb = blk
        acc = jnp.minimum(acc, jnp.min(ab[:, :, None] + bb[None, :, :],
                                       axis=1))
        return acc, None

    init = jnp.full((s_dim, n_dim), jnp.inf, a.dtype)
    out, _ = jax.lax.scan(body, init, (a_blocks, b_blocks))
    return out


def _minplus_blocked(a: jnp.ndarray, b: jnp.ndarray,
                     block_k: int = 256) -> jnp.ndarray:
    """out[s, j] = min_k a[s, k] + b[k, j], accumulated over k blocks."""
    return _minplus_blocks(a, _block_rows(b, block_k))


class QueryEngine:
    """Batched SSD/SSSP execution over a packed :class:`HoDIndex`.

    core_mode:
      * ``"closure"``  — beyond-paper: single tropical matmul (default)
      * ``"bellman"``  — in-JAX iterative min-plus to fixpoint (diameter-
                          bounded), closest in spirit to scanning G_c
      * ``"dijkstra"`` — paper-faithful host-side heap Dijkstra on the core

    Forward/backward sweeps and SSSP reconstruction all run through the
    single SweepPlan executor (:meth:`_run_plan`): one ``lax.scan`` over
    static-shape plan levels.  ``use_pallas`` picks the level kernel —
    the fused ``relax_bucketed`` Pallas kernel vs. its jnp oracle — and
    the core search's tropical matmul flavor; ``interpret`` (default:
    auto, on except on real TPUs) selects Pallas interpret mode so the
    same path runs on CPU.
    """

    #: Optional :class:`repro.obs.trace.Tracer` (DESIGN.md §11) — set by
    #: the streaming engine / server; ``None`` keeps every hook inert.
    tracer = None
    #: While a program traces, the dict :meth:`_core_update` puts the
    #: bellman search's counts in (see :meth:`_forward_core`).
    _core_counts = None

    def __init__(self, index: HoDIndex, core_mode: str = "closure",
                 use_pallas: bool = False, eps: float = 0.0,
                 interpret: Optional[bool] = None, k_cap: int = 16):
        self._init_engine(index, core_mode, use_pallas, eps, interpret)

        index.ensure_plans(k_cap)   # no-op for pack_index/v2+-load indexes
        self._plans = _Plans(_plan_to_device(index.plan_f),
                             _plan_to_device(index.plan_b),
                             _plan_to_device(index.plan_core),
                             jnp.asarray(index.perm, jnp.int32))

        # Each program returns ``(answer, counts)``: counts maps a span
        # tag's name to a scalar or per-row ``[S]`` count (see
        # :func:`_count_tags`), ``{}`` where the program counts nothing.
        self._ssd_jit = _program(self._ssd_impl, self.core_mode)
        self._sssp_jit = _program(self._sssp_impl, self.core_mode)
        self._p2p_jit = _program(self._p2p_impl, self.core_mode)
        self._within_jit = _program(self._within_impl, self.core_mode)

    def _init_engine(self, index: HoDIndex, core_mode: str,
                     use_pallas: bool, eps: float,
                     interpret: Optional[bool]) -> None:
        """Plan-independent engine state: everything a sweep level body
        or core search needs that is NOT a device-resident SweepPlan.
        Shared with the store-backed streaming engine
        (`repro.storage.stream`), which feeds plan levels from the page
        cache instead of uploading them whole."""
        if core_mode not in ("closure", "bellman", "dijkstra"):
            raise ValueError(core_mode)
        if core_mode == "closure" and index.n_core \
                and index.core_closure.shape[0] == 0:
            core_mode = "bellman"   # closure skipped at pack time (big core)
        self.index = index
        self.core_mode = core_mode
        self.use_pallas = use_pallas
        self.interpret = (jax.default_backend() != "tpu"
                          if interpret is None else interpret)
        self.eps = float(eps)

        # Meet-node metadata (DESIGN.md §7): the graph level behind each
        # real plan level, in scan order — derived from the resident
        # chunk arrays, so the store-backed engine gets it without
        # materializing a plan.  P2P / threshold sweeps use it to skip
        # provably-inert levels (everything below the query endpoints).
        self._level_ids_f = plan_level_ids(index, forward=True)
        self._level_ids_b = plan_level_ids(index, forward=False)
        # The [C, C] matrix the jitted core search reads — the closure,
        # or the dense adjacency the bellman mode iterates — passed to
        # every jitted query as an operand.  The dijkstra mode searches
        # the core CSR on the host and needs neither.
        if core_mode == "closure":
            self._core = jnp.asarray(index.core_closure)
        elif core_mode == "bellman":
            self._core = jnp.asarray(_dense_core_adjacency(index))
        else:
            self._core = None

    # ------------------------------------------------------- plan executor
    def _run_plan(self, state: jnp.ndarray, plan, level_body,
                  reverse: bool = False) -> jnp.ndarray:
        """THE sweep executor: one ``lax.scan`` over static plan levels.

        ``level_body(state, dst, src_idx, w, assoc, valid) -> state``
        consumes one ``[M_pad(, K_fix)]`` level slice; ``valid`` is the
        row-validity mask with the level mask already folded in, so
        padding rows and padding levels are inert regardless of the body.
        The scan body traces once — O(1) traces per sweep, not O(levels).
        ``reverse=True`` scans the plan's levels back-to-front (the P2P
        backward-label sweep walks ``plan_b`` in ascending rank order —
        DESIGN.md §7) at the same single trace.
        """
        dst, src_idx, w, assoc, row_valid, level_mask = plan
        if dst.shape[0] == 0:
            return state

        def body(carry, lvl):
            l_dst, l_src, l_w, l_assoc, l_valid, l_mask = lvl
            return level_body(carry, l_dst, l_src, l_w, l_assoc,
                              l_valid & l_mask), None

        state, _ = jax.lax.scan(
            body, state, (dst, src_idx, w, assoc, row_valid, level_mask),
            reverse=reverse)
        return state

    def _run_plan_stream(self, state: jnp.ndarray, levels,
                         step, label: str = "") -> jnp.ndarray:
        """Level-granular donate/feed twin of :meth:`_run_plan`.

        ``levels`` yields host-side ``(dst, src_idx, w, assoc, valid)``
        slabs — typically straight off the store's page cache
        (DESIGN.md §6) — and ``step`` is a jitted level function with
        ``state`` donated, so peak plan memory is one level slab, not
        the whole ``[L_pad, M_pad, K_fix]`` envelope.  Every slab of one
        plan shares a shape, so ``step`` traces once per plan — the
        same O(1)-trace property as the ``lax.scan`` executor.  With a
        tracer, each level's step runs inside a ``level.relax`` span
        tagged ``label`` (the plan name).
        """
        tracer = self.tracer
        for lvl, (dst, src_idx, w, assoc, valid) in enumerate(levels):
            with span_if(tracer, "level.relax", plan=label, level=lvl):
                state = step(state, jnp.asarray(dst),
                             jnp.asarray(src_idx), jnp.asarray(w),
                             jnp.asarray(assoc), jnp.asarray(valid))
        return state

    def _relax_level(self, dist, dst, src_idx, w, assoc, valid):
        """Distance relaxation for one level (SSD sweeps, DESIGN.md §5).

        Within one level the gathered sources and the scattered
        destinations are disjoint (DESIGN.md §3), so gather-then-scatter
        is race-free; rows that split one destination's long in-edge list
        are merged by the scatter-min, and sentinel rows scatter into the
        scrap column (which stays +inf forever).
        """
        del assoc
        cur = dist[:, dst]
        relax = functools.partial(relax_bucketed, use_pallas=self.use_pallas,
                                  interpret=self.interpret)
        new = _per_batch_shard(relax, (True, False, False, True, False),
                               dist, src_idx, w, cur, valid)
        return dist.at[:, dst].min(new)

    def _relax_level_rev(self, dlab, dst, src_idx, w, assoc, valid):
        """Reverse relaxation for one level: backward *labels* (P2P mode,
        DESIGN.md §7).  ``dlab[u]`` is the shortest strictly-descending
        distance from ``u`` to the query target, so each backward edge
        ``(x -> v, w)`` is relaxed against its direction:
        ``dlab[x] = min(dlab[x], w + dlab[v])``.  Gather at ``dst`` (the
        level-defining node, final once its level is reached scanning
        ``plan_b`` in reverse = ascending rank), scatter-min into the
        higher-rank ``src_idx`` slots.  Padding slots carry ``+inf``
        weight and sentinel sources — absorbing, as in the forward body.
        """
        del assoc
        cand = dlab[:, dst][:, :, None] + w[None]        # [S, M, K]
        cand = jnp.where(valid[None, :, None], cand, INF)
        return dlab.at[:, src_idx].min(cand)

    def _relax_level_thresh(self, d):
        """:meth:`_relax_level` with the distance-threshold mask folded
        into the scan body (DESIGN.md §7): any label that exceeds ``d``
        is snapped back to ``+inf`` *inside the sweep*, so it can never
        seed further relaxations.  Sound because weights are positive —
        every prefix of a path with total length ``<= d`` is itself
        ``<= d`` — and exactly what lets the streaming engine skip
        whole levels whose source values are all masked."""
        def body(dist, dst, src_idx, w, assoc, valid):
            dist = self._relax_level(dist, dst, src_idx, w, assoc, valid)
            return jnp.where(dist <= d, dist, INF)

        return body

    def _recon_level(self, pred, dist, dst, src_idx, w, assoc, valid):
        """SSSP predecessor reconstruction for one level (§6): scatter
        the assoc of every tight edge, max-merged (-1 = none).  ``dist``
        is an explicit operand (not a closure) so the streaming engine
        can jit this once and feed per-query distances."""
        cand = dist[:, src_idx] + w[None]            # [S, M, K]
        tgt = dist[:, dst]                           # [S, M]
        tight = jnp.isfinite(cand) \
            & (cand <= (tgt + self.eps * (1.0 + tgt))[..., None])
        tight &= valid[None, :, None]
        pcand = jnp.max(jnp.where(tight, assoc[None], -1), axis=-1)
        return pred.at[:, dst].max(pcand)

    def _recon_level_body(self, dist):
        """:meth:`_recon_level` curried into the plan-executor body
        signature (``dist`` closed over, for the all-on-device path)."""
        def body(pred, dst, src_idx, w, assoc, valid):
            return self._recon_level(pred, dist, dst, src_idx, w, assoc,
                                     valid)

        return body

    # ------------------------------------------------------------------ SSD
    def _core_update(self, dist: jnp.ndarray, core: jnp.ndarray,
                     core_mode: str) -> jnp.ndarray:
        """Core search (§5.2) over ``core``: the dense adjacency in
        bellman mode, the all-pairs closure in closure mode."""
        ix = self.index
        c = ix.n_core
        if c == 0:
            return dist
        lo = ix.n_noncore
        dc = jax.lax.dynamic_slice_in_dim(dist, lo, c, axis=1)
        if core_mode == "bellman":
            # Iterate min-plus relaxation to fixpoint — the closest in-JAX
            # analogue of the paper's in-memory core scan. Converges in at
            # most C-1 rounds; real cores settle in a handful.  The loop
            # counts its rounds (the last one changes nothing) and, per
            # row, the last round in which the row changed.
            blocks = _block_rows(core)

            def cond(state):
                _, changed, it, _ = state
                return changed & (it < c)

            def body(state):
                d, _, it, settle = state
                nd = jnp.minimum(d, _minplus_blocks(d, blocks))
                moved = jnp.any(nd < d, axis=1)
                it = it + 1
                return nd, jnp.any(moved), it, jnp.where(moved, it, settle)

            dc, _, rounds, settle = jax.lax.while_loop(
                cond, body, (dc, jnp.bool_(True), jnp.int32(0),
                             jnp.zeros(dc.shape[0], jnp.int32)))
            if self._core_counts is not None:
                self._core_counts.update(core_rounds=rounds,
                                         core_row_rounds=settle)
        else:  # closure
            if self.use_pallas:
                from ..kernels.tropical_matmul.ops import minplus
                dc = _per_batch_shard(
                    functools.partial(minplus, interpret=self.interpret),
                    (True, False), dc, core)
            else:
                dc = _minplus_blocked(dc, core)
        return jax.lax.dynamic_update_slice_in_dim(dist, dc, lo, axis=1)

    def _init_state(self, nodes_perm: jnp.ndarray) -> jnp.ndarray:
        """[S, n_pad] all-+inf label state with 0 at each row's node.
        Sources are embarrassingly parallel: under an active mesh whose
        rules bind "batch", the state shards over devices and every
        sweep runs data-parallel (no-op without a mesh)."""
        s = nodes_perm.shape[0]
        state = jnp.full((s, self.index.n_pad), INF, jnp.float32)
        state = state.at[jnp.arange(s), nodes_perm].set(0.0)
        return sl.shard(state, "batch", None)

    def _forward_core(self, plans: _Plans, core, sources_perm: jnp.ndarray,
                      core_mode: str, level_body=None):
        """Forward search (§5.1) + core search (§5.2): the shared first
        two phases of SSD, P2P, and threshold queries.  Returns ``(dist,
        counts)``: counts holds the bellman search's ``core_rounds`` and
        per-row ``core_row_rounds`` (each row's settle round), handed
        over by :meth:`_core_update` while it traces, or is ``{}``."""
        dist = self._init_state(sources_perm)
        with jax.named_scope("forward_sweep"):
            dist = self._run_plan(dist, plans.f,
                                  level_body or self._relax_level)
        counts: dict = {}
        if core_mode != "dijkstra":
            self._core_counts = counts
            try:
                with jax.named_scope("core_search"):
                    dist = self._core_update(dist, core, core_mode)
            finally:
                self._core_counts = None
        return dist, counts

    def _ssd_state(self, plans: _Plans, core, sources_perm: jnp.ndarray,
                   core_mode: str):
        """The ``[S, n_pad]`` label state in the index's node order, and
        the program's counts."""
        dist, counts = self._forward_core(plans, core, sources_perm,
                                          core_mode)
        with jax.named_scope("backward_sweep"):         # §5.3
            dist = self._run_plan(dist, plans.b, self._relax_level)
        # Per row, the real nodes left at +inf: columns from ``n`` on are
        # the sentinel and padding.
        with jax.named_scope("count_unreachable"):
            real = jnp.arange(dist.shape[1]) < self.index.n
            unreachable = jnp.sum(jnp.isinf(dist) & real, axis=1,
                                  dtype=jnp.int32)
        return dist, {**counts, "unreachable": unreachable}

    def _ssd_impl(self, plans: _Plans, core, sources_perm: jnp.ndarray,
                  core_mode: str):
        """SSD answers ``[S, n]`` in original node order: the state
        gathered on the device, so the host receives a batch's answer in
        one transfer and makes no pass over it."""
        dist, counts = self._ssd_state(plans, core, sources_perm, core_mode)
        with jax.named_scope("unpermute"):
            return jnp.take(dist, plans.perm, axis=1), counts

    def _p2p_impl(self, plans: _Plans, core, sources_perm: jnp.ndarray,
                  targets_perm: jnp.ndarray, core_mode: str):
        """Meet-in-the-middle P2P distances (DESIGN.md §7).

        Forward labels of ``s`` (forward sweep + core search — exactly
        the SSD front half) meet backward labels of ``t`` (``plan_b``
        scanned in *reverse* = ascending rank with the reversed level
        body), and ``dist(s, t) = min_m fwd[m] + bwd[m]``: by the arch
        property (Theorem 1) every shortest path ascends, optionally
        crosses the core — folded into ``fwd`` by the core search — and
        descends, so some node ``m`` on it carries both labels."""
        fwd, counts = self._forward_core(plans, core, sources_perm,
                                         core_mode)
        with jax.named_scope("backward_labels"):
            bwd = self._init_state(targets_perm)
            bwd = self._run_plan(bwd, plans.b, self._relax_level_rev,
                                 reverse=True)
        with jax.named_scope("meet"):
            return jnp.min(fwd + bwd, axis=1), counts

    def _within_impl(self, plans: _Plans, core, sources_perm: jnp.ndarray,
                     d: jnp.ndarray, core_mode: str):
        """Distance-threshold SSD (DESIGN.md §7): the full sweep pipeline
        with the ``<= d`` mask applied inside every scan body, so labels
        past the threshold die where they arise instead of being
        filtered at the end — the masked levels are what the streaming
        engine skips reading entirely."""
        body = self._relax_level_thresh(d)
        dist, counts = self._forward_core(plans, core, sources_perm,
                                          core_mode, level_body=body)
        dist = jnp.where(dist <= d, dist, INF)          # mask core output
        with jax.named_scope("backward_sweep"):
            return self._run_plan(dist, plans.b, body), counts

    def _sssp_impl(self, plans: _Plans, core, sources_perm: jnp.ndarray,
                   core_mode: str):
        ix = self.index
        dist, counts = self._ssd_state(plans, core, sources_perm,
                                       core_mode)
        s = sources_perm.shape[0]
        pred = jnp.full((s, ix.n_pad), -1, jnp.int32)
        recon = self._recon_level_body(dist)
        # The per-plan reconstruction scatters are max-merges over a
        # fixed `dist`, so the plan order commutes; the store-backed
        # engine exploits this by walking plans in reverse (cache
        # affinity with the distance pass) and stays bit-identical.
        with jax.named_scope("reconstruct"):
            for plan in (plans.f, plans.c, plans.b):
                pred = self._run_plan(pred, plan, recon)
        return (dist, pred), counts

    # ---------------------------------------------------------------- public
    def _served(self, mode: str, rows: int, dispatch, readback):
        """One public call in its three phases (DESIGN.md §11):
        ``dispatch()`` looks the endpoints up, moves them to the device
        and starts the work, returning ``(out, counts)`` as the programs
        do; ``readback(out)`` copies the answer to the host in original
        node order.  With a tracer each phase is a span
        (``engine.dispatch``, ``engine.wait`` — a ``block_until_ready``
        only a traced call makes — and ``engine.readback``) tagged with
        the mode, the batch and fill the server bound (``fill`` defaults
        to ``rows``) and, once read back, the program's counts
        (:func:`_count_tags`)."""
        tracer = self.tracer
        if tracer is None:
            return readback(dispatch()[0])
        tags = {"mode": mode, "fill": rows, **tracer.tags()}
        phases = [tracer.span(name, **tags) for name in
                  ("engine.dispatch", "engine.wait", "engine.readback")]
        with phases[0]:
            out, counts = dispatch()
        with phases[1]:
            jax.block_until_ready((out, counts))
        with phases[2]:
            # The answer's path is the untraced one; the counts, a few
            # ints, follow it in a transfer of their own.
            answer = readback(out)
            counted = _count_tags(jax.device_get(counts), tags["fill"])
        for span in phases:       # a span's tags are read when exported
            span.attrs.update(counted)
        return answer

    def _ends(self, nodes: np.ndarray) -> jnp.ndarray:
        """Query endpoints in the index's node order, on the device."""
        return jnp.asarray(self.index.perm[nodes])

    def _unpermute(self, dist) -> np.ndarray:
        """A ``[S, n_pad]`` state on the host in original node order,
        row-major: ``np.take`` gathers along each row, where ``[:, perm]``
        would build a column-major array, with every row strided."""
        return np.take(np.asarray(dist), self.index.perm, axis=1)

    def ssd(self, sources: np.ndarray) -> np.ndarray:
        """Distances from each source to every node, original node order."""
        sources = np.asarray(sources, dtype=np.int32)
        if self.core_mode == "dijkstra":
            return self._unpermute(
                self._dijkstra_path(self.index.perm[sources]))
        return self._served(
            "ssd", len(sources),
            lambda: self._ssd_jit(self._plans, self._core,
                                  self._ends(sources)),
            np.asarray)

    def sssp(self, sources: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(dist, pred): pred[v] = node preceding v on a shortest path, -1
        for sources/unreachable. Node ids in original order."""
        sources = np.asarray(sources, dtype=np.int32)
        if self.core_mode == "dijkstra":
            # The host-Dijkstra core search lives outside the jit'd
            # pipeline; run it first, then reconstruction over the same
            # plans (eagerly — this mode is for validation, not serving).
            dist = jnp.asarray(self._dijkstra_path(self.index.perm[sources]))
            pred = jnp.full((dist.shape[0], self.index.n_pad), -1,
                            jnp.int32)
            recon = self._recon_level_body(dist)
            for plan in (self._plans.f, self._plans.c, self._plans.b):
                pred = self._run_plan(pred, plan, recon)
            return self._unpermute(dist), self._unpermute(pred)
        return self._served(
            "sssp", len(sources),
            lambda: self._sssp_jit(self._plans, self._core,
                                   self._ends(sources)),
            lambda out: tuple(self._unpermute(x) for x in out))

    def p2p(self, sources: np.ndarray, targets: np.ndarray) -> np.ndarray:
        """Point-to-point distances ``dist(sources[i], targets[i])``
        (meet-in-the-middle, DESIGN.md §7) — a ``[S]`` float32 vector.

        Exact: bit-identical to ``ssd(sources)[i, targets[i]]`` (the
        meet combine and the backward sweep compose the same (min, +)
        sums over the same augmented edges).
        """
        sources = np.asarray(sources, dtype=np.int32)
        targets = np.asarray(targets, dtype=np.int32)
        if self.core_mode == "dijkstra":
            fwd = self._dijkstra_forward_core(self.index.perm[sources])
            bwd = self._init_state(self._ends(targets))
            bwd = self._run_plan(bwd, self._plans.b, self._relax_level_rev,
                                 reverse=True)
            return np.asarray(jnp.min(jnp.asarray(fwd) + bwd, axis=1))
        return self._served(
            "p2p", len(sources),
            lambda: self._p2p_jit(self._plans, self._core,
                                  self._ends(sources), self._ends(targets)),
            np.asarray)

    def ssd_within(self, sources: np.ndarray, d: float) -> np.ndarray:
        """Distance-threshold query (DESIGN.md §7): distances from each
        source in original node order, with every entry beyond ``d``
        masked to ``+inf`` — nodes within the threshold carry exactly
        their SSD distance.  ``d`` is a traced operand, so changing the
        threshold never recompiles."""
        sources = np.asarray(sources, dtype=np.int32)
        if self.core_mode != "dijkstra":
            return self._served(
                "within", len(sources),
                lambda: self._within_jit(self._plans, self._core,
                                         self._ends(sources),
                                         jnp.float32(d)),
                self._unpermute)
        body = self._relax_level_thresh(jnp.float32(d))
        dist = self._init_state(self._ends(sources))
        dist = self._run_plan(dist, self._plans.f, body)
        dist = self._core_dijkstra_host(np.array(dist))
        dist = jnp.where(jnp.asarray(dist) <= d, jnp.asarray(dist), INF)
        dist = self._run_plan(dist, self._plans.b, body)
        return self._unpermute(dist)

    def knn(self, sources: np.ndarray, k: int
            ) -> Tuple[np.ndarray, np.ndarray]:
        """The ``k`` nearest nodes of each source (DESIGN.md §7):
        ``(nodes, dist)``, each ``[S, k]``, ascending by ``(distance,
        node id)`` with the source itself included at distance 0; rows
        with fewer than ``k`` reachable nodes pad with ``(-1, +inf)``.

        In-memory reference: a full SSD sweep + host top-k selection.
        The streaming engine's bounded-sweep variant
        (`repro.storage.stream`) is bit-identical.
        """
        if not 1 <= k <= self.index.n:
            raise ValueError(f"k must be in [1, {self.index.n}], got {k}")
        return _knn_select(self.ssd(sources), k)

    def paths(self, sources: np.ndarray, targets: np.ndarray) -> list:
        """Unfold predecessors into explicit node paths (one per source)."""
        dist, pred = self.sssp(sources)
        out = []
        for i, t in enumerate(np.asarray(targets).tolist()):
            if not np.isfinite(dist[i, t]):
                out.append(None)
                continue
            path = [t]
            guard = 0
            while pred[i, path[-1]] >= 0 and guard <= self.index.n:
                path.append(int(pred[i, path[-1]]))
                guard += 1
            out.append(path[::-1])
        return out

    # ----------------------------------------------- paper-faithful Dijkstra
    def _core_dijkstra_host(self, dist: np.ndarray) -> np.ndarray:
        """Host heap Dijkstra on the core CSR for every batch row — the
        literal §5.2 in-memory core search.  Mutates and returns the
        writable ``[S, n_pad]`` host array; shared by the in-memory
        validation mode and the store-backed streaming engine."""
        ix = self.index
        lo, c = ix.n_noncore, ix.n_core
        for i in range(dist.shape[0]):
            dc = dist[i, lo:lo + c].copy()
            heap = [(float(d), int(v)) for v, d in enumerate(dc)
                    if np.isfinite(d)]
            heapq.heapify(heap)
            done = np.zeros(c, dtype=bool)
            while heap:
                d_u, u = heapq.heappop(heap)
                if done[u] or d_u > dc[u]:
                    continue
                done[u] = True
                e0, e1 = ix.core_ptr[u], ix.core_ptr[u + 1]
                for v, wv in zip(ix.core_dst[e0:e1], ix.core_w[e0:e1]):
                    nd = d_u + float(wv)
                    if nd < dc[v]:
                        dc[v] = nd
                        heapq.heappush(heap, (nd, int(v)))
            dist[i, lo:lo + c] = dc
        return dist

    def _dijkstra_forward_core(self, sources_perm: np.ndarray) -> np.ndarray:
        """Forward plan sweep (JAX) -> host heap Dijkstra on G_c: the
        shared front half of the paper-faithful SSD and P2P pipelines."""
        dist = self._init_state(jnp.asarray(sources_perm))
        dist = np.array(self._run_plan(dist, self._plans.f,
                                       self._relax_level))  # writable copy
        return self._core_dijkstra_host(dist)

    def _dijkstra_path(self, sources_perm: np.ndarray) -> np.ndarray:
        """Forward plan sweep (JAX) -> host heap Dijkstra on G_c ->
        backward plan sweep (JAX): the literal §5 pipeline, used as a
        validation mode."""
        dist = self._dijkstra_forward_core(sources_perm)
        return np.asarray(self._run_plan(jnp.asarray(dist), self._plans.b,
                                         self._relax_level))


def dijkstra_reference(g, sources) -> np.ndarray:
    """Plain in-memory Dijkstra oracle on the *original* graph."""
    n = g.n
    out = np.full((len(sources), n), np.inf, dtype=np.float64)
    for i, s in enumerate(np.asarray(sources).tolist()):
        dist = out[i]
        dist[s] = 0.0
        heap = [(0.0, s)]
        while heap:
            d_u, u = heapq.heappop(heap)
            if d_u > dist[u]:
                continue
            dsts, ws = g.out_edges(u)
            for v, wv in zip(dsts.tolist(), ws.tolist()):
                nd = d_u + wv
                if nd < dist[v]:
                    dist[v] = nd
                    heapq.heappush(heap, (nd, v))
    return out
