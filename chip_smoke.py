#!/usr/bin/env python3
"""Bring-up smoke of the HoD query server on a TPU.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # only the four-chip data-parallel path

Everything runs in this one process, and every input is built from a
seed.  On one chip the phases are:

1. device: the first JAX device must be a TPU; there is no CPU fallback;
2. graph: a 256x256 road grid (65,536 nodes), built and packed exactly
   as the serve CLI does (``repro.launch.serve.build_served_index``);
3. served, in memory: ``QueryServer(QueryEngine(ix, use_pallas=True))``
   answers SSD, SSSP and P2P batches with compiled (not interpreted)
   Pallas kernels;
4. served, store-backed: the index saved with the raw codec behind a 25%
   page cache, serving ``configs/serve_mixed.yaml``'s ssd:1 / p2p:3 mix
   under the slo scheduler; answers bit-identical to phase 3;
5. correctness: sampled SSD rows against the Dijkstra oracle, and the
   Pallas engine against the jnp engine bit for bit;
6. the tropical min-plus kernel on a closure-mode core (48x48 grid),
   and ``tpu_custom_call`` in the compiled programs of both kernels;
7. memory: the device's ``peak_bytes_in_use``.

``--chips 4`` runs the path ``serve --data-parallel`` takes: the side-256
SSD batch sharded four ways over a ``data`` mesh, against the one-chip
answers, which it must equal bit for bit.

Each phase prints one line.  A failed check raises, so the exit code is
non-zero.  The last line of a passing run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: Side of the served road grid on the chip.
CHIP_SIDE = 256
#: Side of the grid whose core is small enough for closure mode: the
#: benchmark suite's USRN-like graph (``benchmarks/common.py``).
TROPICAL_SIDE = 48
BATCH = 16
#: Requests in the phase-4 mixed stream.
MIXED_REQUESTS = 64


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(f"phase {phase}: "
          + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def timed(fn, *args, **kwargs):
    """``(result, seconds)``, the clock stopped after the result is ready."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0


def secs(values) -> str:
    return "[" + ",".join(f"{v:.4f}" for v in values) + "]"


def build(side: int):
    """Phase 2: the road grid and its served index."""
    from repro.core import grid_road_graph
    from repro.launch.serve import build_served_index

    g = grid_road_graph(side, seed=1)
    (ix, _), build_s = timed(build_served_index, g)
    say("2 graph", side=side, n=g.n, m=g.m, levels=ix.n_levels,
        core=ix.n_core, plan_bytes=ix.plan_bytes(),
        build_s=f"{build_s:.2f}")
    return g, ix


def _path_length(g, path) -> float:
    total = 0.0
    for u, v in zip(path[:-1], path[1:]):
        dst, w = g.out_edges(u)
        hit = np.nonzero(dst == v)[0]
        check(hit.size > 0, f"path hop {u}->{v} is not an edge")
        total += float(w[hit].min())
    return total


def _fill(head, draw, batches: int = 3):
    """``head`` topped up by ``draw(k)`` to a whole number of batches,
    at least ``batches`` of them."""
    total = max(batches * BATCH, -(-len(head) // BATCH) * BATCH)
    return np.asarray(list(head) + draw(total - len(head)).tolist(),
                      np.int32)


def _batched(fn, *columns):
    """``fn`` applied to ``BATCH``-row slices of ``columns``, so every
    call has the compiled batch shape; results concatenated."""
    return np.concatenate([fn(*(c[lo:lo + BATCH] for c in columns))
                           for lo in range(0, len(columns[0]), BATCH)])


def _serve_batches(server, requests, mode: str):
    """Serve ``requests`` one batch at a time: ``(results, per-batch s)``."""
    results, times = [], []
    for lo in range(0, len(requests), BATCH):
        out, dt = timed(server.serve_stream, requests[lo:lo + BATCH],
                        mode=mode)
        results += out
        times.append(dt)
    return results, times


def run_phases(side: int, workdir: str, seed: int = 0) -> None:
    """Phases 2-7 on the default device.  ``workdir`` receives the
    phase-4 store and is left as it was found."""
    from benchmarks.common import BUILD_CFG
    from repro.config import SERVE_DEFAULTS, Config
    from repro.core import (QueryEngine, dijkstra_reference,
                            grid_road_graph, pack_index)
    from repro.core.build_fast import build_hod_fast
    from repro.core.query import _minplus_blocked
    from repro.kernels.edge_relax.ops import relax_bucketed
    from repro.kernels.tropical_matmul.ops import minplus
    from repro.launch.serve import (QueryServer, mixed_request_stream,
                                    server_from_config)
    from repro.storage import segment_logical_bytes

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(seed)
    g, ix = build(side)

    # The phase-4 traffic, drawn first so phase 3 answers the same keys.
    cfg = Config(os.path.join(ROOT, "configs", "serve_mixed.yaml"),
                 defaults=SERVE_DEFAULTS,
                 overrides={"serve": {"use_pallas": True}})
    check(cfg.get("serve.scheduler") == "slo", "serve_mixed.yaml is slo")
    stream = mixed_request_stream(cfg, g.n, MIXED_REQUESTS, rng)
    ssd_src = _fill([a[0] for m, a in stream if m == "ssd"],
                    lambda k: rng.integers(0, g.n, k))
    pairs = _fill(sorted({a for m, a in stream if m == "p2p"}),
                  lambda k: rng.integers(0, g.n, (k, 2)))

    # ---- phase 3: served path, in memory -------------------------------
    eng = QueryEngine(ix, use_pallas=True)
    check(eng.interpret is (not on_tpu),
          f"interpret={eng.interpret} on backend {jax.default_backend()}")
    _, first_s = timed(eng.ssd, ssd_src[:BATCH])
    _, steady_s = timed(eng.ssd, ssd_src[:BATCH])
    server, warm_s = timed(QueryServer, eng, batch_size=BATCH,
                           modes=("ssd", "sssp", "p2p"), warm_start=True)
    ssd_res, ssd_t = _serve_batches(server, ssd_src, "ssd")
    p2p_res, p2p_t = _serve_batches(server, pairs, "p2p")
    sssp_res, sssp_t = _serve_batches(server, ssd_src[:BATCH], "sssp")
    ssd_rows = {r.source: r.dist for r in ssd_res}
    p2p_rows = {(r.source, r.target): r.dist for r in p2p_res}
    for r in sssp_res:
        check(np.array_equal(r.dist, ssd_rows[r.source]),
              f"sssp dist != ssd dist for source {r.source}")
    targets = rng.integers(0, g.n, BATCH)
    paths, paths_s = timed(eng.paths, ssd_src[:BATCH], targets)
    for s, t, path in zip(ssd_src[:BATCH].tolist(), targets.tolist(),
                          paths):
        check(path is not None and path[0] == s and path[-1] == t,
              f"path {s}->{t} is {path}")
        check(_path_length(g, path) == float(ssd_rows[s][t]),
              f"path {s}->{t} length != its distance")
    say("3 served in-memory", interpret=eng.interpret,
        core_mode=eng.core_mode, ssd_first_call_s=f"{first_s:.3f}",
        ssd_steady_s=f"{steady_s:.4f}", warm_start_s=f"{warm_s:.3f}",
        ssd_batch_s=secs(ssd_t), p2p_batch_s=secs(p2p_t),
        sssp_batch_s=secs(sssp_t), paths_s=f"{paths_s:.3f}")
    sssp_rows = {r.source: r.pred for r in sssp_res}
    del server, eng
    gc.collect()   # the engine's jitted closures form a reference cycle

    # ---- phase 4: served path, store-backed ----------------------------
    store_dir = os.path.join(workdir, "store")
    shutil.rmtree(store_dir, ignore_errors=True)
    ix.save_store(store_dir, codec="raw")
    try:
        budget = int(0.25 * segment_logical_bytes(store_dir))
        server = server_from_config(cfg, store_path=store_dir,
                                    cache_bytes=budget)

        async def drive():
            tasks = [asyncio.create_task(server.submit(*a, mode=m))
                     for m, a in stream]
            await asyncio.sleep(0)
            await server.drain()
            return await asyncio.gather(*tasks)

        try:
            _, warm_s = timed(server.warmup)
            answers, mixed_s = timed(asyncio.run, drive())
        finally:
            server.close()
        st = server.stats
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    for (m, a), r in zip(stream, answers):
        want = ssd_rows[a[0]] if m == "ssd" else p2p_rows[a]
        check(np.array_equal(r.dist, want),
              f"store-backed {m}{a} differs from in-memory")
    say("4 served store-backed", scheduler=server.scheduler,
        requests=len(stream), batches=st.batches, cache_budget=budget,
        page_hit_rate=f"{st.page_hit_rate():.3f}",
        deadline_misses=st.deadline_misses, warmup_s=f"{warm_s:.3f}",
        mixed_s=f"{mixed_s:.3f}", bit_identical=True)
    del server
    gc.collect()

    # ---- phase 5: correctness ------------------------------------------
    sample = ssd_src[:4]
    oracle = dijkstra_reference(g, sample)
    # f32 sums along a path of at most 2 * side hops, each rounded once
    rtol = 4 * side * float(np.finfo(np.float32).eps)
    for s, want in zip(sample.tolist(), oracle):
        got = ssd_rows[s][:g.n]
        check(np.array_equal(np.isfinite(got), np.isfinite(want)),
              f"reachability of source {s} differs from Dijkstra")
        fin = np.isfinite(want)
        check(np.allclose(got[fin], want[fin], rtol=rtol, atol=0.0),
              f"SSD row of source {s} differs from Dijkstra")
    ref = QueryEngine(ix, use_pallas=False)
    d_ref = _batched(ref.ssd, ssd_src)
    check(all(np.array_equal(d_ref[i], ssd_rows[s])
              for i, s in enumerate(ssd_src.tolist())),
          "Pallas SSD != jnp SSD")
    p_ref = _batched(ref.p2p, pairs[:, 0], pairs[:, 1])
    check(all(np.array_equal(p_ref[i], p2p_rows[(s, t)])
              for i, (s, t) in enumerate(pairs.tolist())),
          "Pallas P2P != jnp P2P")
    _, pred_ref = ref.sssp(ssd_src[:BATCH])
    check(all(np.array_equal(pred_ref[i], sssp_rows[s])
              for i, s in enumerate(ssd_src[:BATCH].tolist())),
          "Pallas SSSP predecessors != jnp")
    say("5 correctness", oracle_rows=len(sample), rtol=f"{rtol:.2e}",
        pallas_equals_jnp="ssd,p2p,sssp")
    # One forward-plan level of the side-256 index, for phase 6's compile.
    relax_shapes = (
        jax.ShapeDtypeStruct((BATCH, ix.n_pad), jnp.float32),
        jax.ShapeDtypeStruct(ix.plan_f.src_idx.shape[1:], jnp.int32),
        jax.ShapeDtypeStruct(ix.plan_f.w.shape[1:], jnp.float32),
        jax.ShapeDtypeStruct((BATCH, ix.plan_f.m_pad), jnp.float32),
        jax.ShapeDtypeStruct((ix.plan_f.m_pad,), jnp.bool_))
    del ref, ix
    gc.collect()

    # ---- phase 6: tropical kernel on a closure-mode core ---------------
    g6 = grid_road_graph(TROPICAL_SIDE, seed=1)
    ix6 = pack_index(g6, build_hod_fast(g6, BUILD_CFG), chunk=2048)
    eng6 = QueryEngine(ix6, use_pallas=True)
    check(eng6.core_mode == "closure",
          f"core of {ix6.n_core} nodes is not in closure mode")
    closure = jnp.asarray(ix6.core_closure)
    rows = closure[rng.integers(0, ix6.n_core, BATCH)]
    got = minplus(rows, closure, interpret=eng6.interpret)
    check(np.array_equal(np.asarray(got),
                         np.asarray(_minplus_blocked(rows, closure))),
          "minplus kernel != _minplus_blocked")
    src6 = rng.integers(0, g6.n, BATCH).astype(np.int32)
    d6 = eng6.ssd(src6)
    check(np.array_equal(d6, QueryEngine(ix6).ssd(src6)),
          "closure-mode Pallas SSD != jnp SSD")
    check(np.allclose(d6[:4, :g6.n], dijkstra_reference(g6, src6[:4]),
                      rtol=4 * TROPICAL_SIDE
                      * float(np.finfo(np.float32).eps)),
          "closure-mode SSD differs from Dijkstra")
    kernels = {}
    if not eng6.interpret:
        kernels["minplus"] = minplus.lower(
            rows, closure, interpret=False).compile().as_text()
        kernels["edge_relax"] = relax_bucketed.lower(
            *relax_shapes, use_pallas=True,
            interpret=False).compile().as_text()
        for name, hlo in kernels.items():
            check("tpu_custom_call" in hlo,
                  f"compiled {name} holds no tpu_custom_call")
    say("6 tropical", side=TROPICAL_SIDE, core=ix6.n_core,
        minplus_equals_blocked=True,
        tpu_custom_call=",".join(kernels) or "interpreted")

    # ---- phase 7: memory -----------------------------------------------
    stats = jax.devices()[0].memory_stats() or {}
    say("7 memory",
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
        bytes_limit=stats.get("bytes_limit", "not reported"))


def run_data_parallel(side: int, devices, seed: int = 0) -> None:
    """The ``serve --data-parallel`` path: one SSD batch sharded over a
    ``data`` mesh of ``devices``, equal bit for bit to one device."""
    from repro import shardlib as sl
    from repro.core import QueryEngine
    from repro.launch.serve import QueryServer

    g, ix = build(side)
    sources = np.random.default_rng(seed).integers(
        0, g.n, BATCH).astype(np.int32)

    eng = QueryEngine(ix, use_pallas=True)
    one, one_first_s = timed(eng.ssd, sources)
    _, one_s = timed(eng.ssd, sources)
    del eng
    gc.collect()

    mesh = sl.make_mesh((len(devices),), ("data",), devices=devices)
    eng = QueryEngine(ix, use_pallas=True)
    server = QueryServer(eng, batch_size=BATCH, cache_entries=0)
    with sl.axis_rules(mesh, {"batch": "data"}):
        res, dp_first_s = timed(server.serve_stream, sources)
        res, dp_s = timed(server.serve_stream, sources)
        raw = eng._ssd_jit(eng._plans, eng._core,
                           jnp.asarray(ix.perm[sources]))
    rows_per_device = {s.data.shape[0] for s in raw.addressable_shards}
    check(len(raw.sharding.device_set) == len(devices)
          and rows_per_device == {BATCH // len(devices)},
          f"state not split over {len(devices)} devices: {raw.sharding}")
    check(all(np.array_equal(r.dist, one[i]) for i, r in enumerate(res)),
          "data-parallel SSD != one-device SSD")
    say("dp", devices=len(devices), rows_per_device=BATCH // len(devices),
        one_first_call_s=f"{one_first_s:.3f}", one_batch_s=f"{one_s:.4f}",
        dp_first_batch_s=f"{dp_first_s:.3f}", dp_batch_s=f"{dp_s:.4f}",
        bit_identical=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel path over four "
                         "chips and the one-chip answers it must equal")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    say("1 device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        run_data_parallel(CHIP_SIDE, devices[:4])
    else:
        run_phases(CHIP_SIDE, os.path.join(ROOT, ".smoke_store"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
