"""ISSUE-8 observability spine: tracing + metrics (DESIGN.md §11).

Two contracts under test.  *Metrics*: fixed-bucket histograms report
percentiles within one bucket of numpy's exact answer, the registry
refuses type-shadowed names, and snapshots are schema-versioned.
*Tracing*: spans nest per thread/track, the Chrome export satisfies
the validator Perfetto relies on, and a tracer attached to a serving
engine is a pure observer — bit-identical answers, the hooks only
watch.
"""
import json
import threading

import numpy as np
import pytest

from repro.core import BuildConfig, build_hod, gnm_random_digraph, pack_index
from repro.launch.serve import QueryServer
from repro.obs import (LATENCY_BUCKETS_MS, REGISTRY, SCHEMA_VERSION,
                       Histogram, MetricsRegistry, Tracer, exp_buckets,
                       span_if, validate_chrome_trace)


# ------------------------------------------------------------- metrics
def test_exp_buckets_shape_and_validation():
    b = exp_buckets(0.05, 60000, 2 ** 0.5)
    assert list(b) == sorted(b) and b[0] == pytest.approx(0.05)
    assert b[-1] >= 60000 / 2 ** 0.5 and len(b) < 60
    assert LATENCY_BUCKETS_MS == b
    with pytest.raises(ValueError):
        exp_buckets(0.0, 100, 2.0)
    with pytest.raises(ValueError):
        exp_buckets(1.0, 100, 1.0)
    with pytest.raises(ValueError):
        Histogram([3.0, 2.0, 1.0])
    with pytest.raises(ValueError):
        Histogram([])


def test_histogram_percentiles_match_numpy_within_a_bucket():
    rng = np.random.default_rng(7)
    xs = rng.lognormal(mean=2.0, sigma=1.0, size=5000)  # ms-ish spread
    h = Histogram(LATENCY_BUCKETS_MS)
    for x in xs:
        h.observe(float(x))
    assert h.count == len(xs)
    assert h.mean() == pytest.approx(float(np.mean(xs)))
    bounds = np.asarray(LATENCY_BUCKETS_MS)
    for q in (0.50, 0.95, 0.99):
        exact = float(np.quantile(xs, q))
        got = h.percentile(q)
        # within one bucket of the truth: the exact value's bucket or
        # a neighbour (interpolation can land either side of an edge)
        i = int(np.searchsorted(bounds, exact))
        lo = bounds[max(i - 1, 0)] if i else 0.0
        hi = bounds[min(i + 1, len(bounds) - 1)]
        assert lo <= got <= hi, (q, exact, got, lo, hi)


def test_histogram_empty_and_overflow():
    h = Histogram([1.0, 2.0])
    assert h.count == 0 and h.percentile(0.99) == 0.0 and h.mean() == 0.0
    h.observe(100.0)                       # beyond the last bound
    assert h.count == 1
    assert h.percentile(0.5) == pytest.approx(2.0)   # clamped to top edge
    s = h.summary()
    assert s["count"] == 1 and s["p50"] <= s["p95"] <= s["p99"]


def test_registry_create_or_fetch_and_type_guard():
    reg = MetricsRegistry()
    c = reg.counter("a.requests")
    c.inc()
    c.inc(2.5)
    assert reg.counter("a.requests") is c and c.value == 3.5
    reg.gauge("a.depth").set(4)
    reg.histogram("a.lat").observe(1.0)
    with pytest.raises(TypeError):
        reg.gauge("a.requests")            # name exists as a Counter
    with pytest.raises(TypeError):
        reg.counter("a.lat")
    snap = reg.snapshot()
    assert snap["schema_version"] == SCHEMA_VERSION
    assert snap["counters"]["a.requests"] == 3.5
    assert snap["gauges"]["a.depth"] == 4
    h = snap["histograms"]["a.lat"]
    assert h["count"] == 1 \
        and len(h["bucket_counts"]) == len(h["bounds"]) + 1  # + overflow
    json.dumps(snap)                       # JSON-able end to end
    reg.reset()
    assert reg.counter("a.requests") is c and c.value == 0
    assert reg.histogram("a.lat").count == 0
    assert isinstance(REGISTRY, MetricsRegistry)


def test_histograms_prefix_listing():
    reg = MetricsRegistry()
    reg.histogram("latency_ms.ssd").observe(1.0)
    reg.histogram("latency_ms.p2p").observe(1.0)
    reg.histogram("coalesce_wait_ms").observe(1.0)
    names = sorted(reg.histograms("latency_ms.").keys())
    assert names == ["latency_ms.p2p", "latency_ms.ssd"]


# ------------------------------------------------------------- tracing
def test_tracer_spans_nest_and_sequence_is_shape_only():
    tr = Tracer()
    with tr.span("outer", plan="f"):
        with tr.span("inner", level=0):
            tr.instant("cache.hit", track="submit", block=3)
        tr.complete("wait", tr.now() - 1000, waiters=2)
    me = threading.current_thread().name
    assert tr.sequence(me) == [
        ("B", "outer", (("plan", "f"),)),
        ("B", "inner", (("level", 0),)),
        ("X", "wait", (("waiters", 2),)),
        ("E", "outer", ()),
    ] or tr.sequence(me)[2][1] == "inner"  # E inner precedes X wait
    # materialized intervals nest: inner within outer, X carries dur
    sp = {s["name"]: s for s in tr.spans()}
    assert sp["outer"]["t0"] <= sp["inner"]["t0"] \
        and sp["inner"]["t1"] <= sp["outer"]["t1"]
    assert sp["wait"]["t1"] - sp["wait"]["t0"] >= 1000
    assert sp["cache.hit"] if "cache.hit" in sp else True
    # instants on a synthetic track keep their own sequence
    assert tr.sequence("submit") == [("i", "cache.hit", (("block", 3),))]
    tr.clear()
    assert tr.events() == []


def test_span_if_is_inert_when_off():
    with span_if(None, "anything", level=1):
        pass                               # no tracer, no error
    tr = Tracer()
    with span_if(tr, "x", track="t"):
        pass
    assert [e["ph"] for e in tr.events()] == ["B", "E"]


def test_chrome_export_validates_and_doctored_docs_fail():
    tr = Tracer()
    with tr.span("a"):
        with tr.span("b"):
            tr.instant("i1")
    tr.complete("x1", tr.now())
    doc = tr.chrome()
    assert validate_chrome_trace(doc) == []
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert [e["name"] for e in evs if e["ph"] == "B"] == ["a", "b"]
    assert all(e["ph"] != "i" or e["s"] == "t" for e in evs)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == \
        threading.current_thread().name

    def doctor(mutate):
        d = json.loads(json.dumps(tr.chrome()))
        mutate(d["traceEvents"])
        return validate_chrome_trace(d)

    assert validate_chrome_trace({}) \
        == ["traceEvents missing or not a list"]
    assert doctor(lambda evs: evs[1].pop("ts"))          # missing field
    last_e = lambda evs: next(i for i in range(len(evs) - 1, -1, -1)  # noqa: E731
                              if evs[i]["ph"] == "E")
    assert doctor(lambda evs: evs.pop(last_e(evs)))      # unbalanced B/E
    assert doctor(lambda evs: evs[last_e(evs)].update(
        name="zzz"))                                     # name mismatch
    assert doctor(lambda evs: evs[-1].update(ts=-1.0))   # ts backwards
    assert doctor(lambda evs: [e.pop("dur") for e in evs
                               if e["ph"] == "X"])       # X without dur
    assert doctor(lambda evs: evs.append(
        {"name": "q", "ph": "E", "pid": 1, "tid": 99,
         "ts": 1e12}))                                   # E without B


def test_jsonl_export_round_trips(tmp_path):
    tr = Tracer()
    with tr.span("a", k=1):
        tr.instant("i", track="t")
    p = tmp_path / "t.jsonl"
    tr.write_jsonl(str(p))
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert [ln["ph"] for ln in lines] == ["B", "i", "E"]
    assert lines[0]["args"] == {"k": 1}
    assert lines[1]["tkey"] == ["track", "t"]


# ----------------------------------------------- serving integration
@pytest.fixture(scope="module")
def engine_ix():
    g = gnm_random_digraph(120, 480, seed=9, weighted=True)
    res = build_hod(g, BuildConfig(max_core_nodes=24, max_core_edges=512,
                                   seed=0))
    return pack_index(g, res, chunk=64)


def _serve(ix, tracer, metrics=None, mode="ssd", n=6):
    from repro.core import QueryEngine
    rng = np.random.default_rng(1)
    src = rng.choice(ix.n, size=n, replace=False).astype(np.int32)
    reqs = (np.stack([src, src[::-1]], axis=1) if mode == "p2p" else src)
    server = QueryServer(QueryEngine(ix), batch_size=3, cache_entries=0,
                         mode=mode, warm_start=True, tracer=tracer,
                         metrics=metrics)
    out = [np.atleast_1d(r.dist) for r in server.serve_stream(reqs)]
    return out, server


def test_tracer_is_a_pure_observer_in_memory(engine_ix):
    tr, reg = Tracer(), MetricsRegistry()
    traced, server = _serve(engine_ix, tr, reg)
    plain, _ = _serve(engine_ix, None)
    for a, b in zip(traced, plain):
        np.testing.assert_array_equal(a, b)
    names = {e["name"] for e in tr.events()}
    assert {"query.ssd", "engine.dispatch", "engine.wait",
            "engine.readback", "server.resolve"} <= names
    assert validate_chrome_trace(tr.chrome()) == []
    # the per-mode latency histogram saw every request
    h = reg.histogram("latency_ms.ssd")
    assert h.count == len(traced)
    assert reg.counter("server.requests").value == len(traced)
    # report() folds the histogram into the human summary
    rep = server.stats.report(label="ssd", batch_size=3, latency=h)
    assert rep.startswith(f"served {len(traced)} ssd requests")
    assert "batch=3" in rep and "latency: mean" in rep
    assert "p99" in rep and "queries/s" in rep
    # without a histogram the latency line is simply absent
    assert "latency:" not in server.stats.report()


def test_coalesced_batch_traces_wait_and_metrics(engine_ix):
    """The async submit path retroactively stamps one ``coalesce.wait``
    X-span per flushed batch (how long requests pooled before the
    engine ran) and feeds the ``coalesce_wait_ms`` histogram."""
    import asyncio

    from repro.core import QueryEngine

    tr, reg = Tracer(), MetricsRegistry()
    server = QueryServer(QueryEngine(engine_ix), batch_size=4,
                         max_wait_ms=5.0, cache_entries=0,
                         warm_start=True, tracer=tr, metrics=reg)

    async def drive():
        tasks = [asyncio.create_task(server.submit(s))
                 for s in range(4)]
        await server.drain()
        return await asyncio.gather(*tasks)

    results = asyncio.run(drive())
    assert len(results) == 4
    waits = [e for e in tr.events() if e["name"] == "coalesce.wait"]
    assert waits and all(e["ph"] == "X" and e["dur"] >= 0
                         for e in waits)
    assert waits[0]["args"]["waiters"] == 4
    assert reg.histogram("coalesce_wait_ms").count == len(waits)
    assert validate_chrome_trace(tr.chrome()) == []


def test_server_writes_trace_and_metrics_files(engine_ix, tmp_path):
    tr, reg = Tracer(), MetricsRegistry()
    _serve(engine_ix, tr, reg, mode="p2p")
    trace_path = tmp_path / "trace.json"
    tr.write_chrome(str(trace_path))
    doc = json.loads(trace_path.read_text())
    assert validate_chrome_trace(doc) == []
    assert any(e["name"] == "query.p2p" for e in doc["traceEvents"])
    metrics_path = tmp_path / "metrics.json"
    with open(metrics_path, "w") as f:
        json.dump(reg.snapshot(), f)
    snap = json.loads(metrics_path.read_text())
    assert snap["schema_version"] == SCHEMA_VERSION
    assert snap["histograms"]["latency_ms.p2p"]["count"] > 0
