"""The yardstick's arithmetic on inputs whose answers are known: window
and percentile rules, the trace reduction, the least-work count and the
peak table, the generators and the reference."""
import hashlib
import json
import math
import os

import numpy as np
import pytest
from benchsupport import ROOT, copy_benchmark
from yardstick import (graphs, peaks, reference, spec, stats, tracing,
                       traffic, work)
from yardstick.loops import Answered, LoopResult


def _answered(rows):
    return LoopResult(start=rows[0][0], end=max(r[2] for r in rows),
                      answered=[Answered(i, due, sent, done)
                                for i, (due, sent, done) in enumerate(rows)])


def test_closed_loop_rate_is_answers_over_the_whole_span():
    # two whole batches of 4: 0 -> 9 s and 9 -> 19 s
    rows = [(0.0, 0.0, 9.0)] * 4 + [(9.0, 9.0, 19.0)] * 4
    res = _answered(rows)
    assert stats.closed_loop_metrics(res, ["sources_per_s"]) == {
        "sources_per_s": 8 / 19.0}
    with pytest.raises(ValueError):
        stats.closed_loop_metrics(res, ["p2p_p50_ms"])


def test_open_loop_latency_runs_from_the_due_time():
    # due every second; each sent late by 0.5 s and answered 1 s later
    rows = [(float(i), i + 0.5, i + 1.5 + 0.1 * i) for i in range(11)]
    res = _answered(rows)
    lat = [(1.5 + 0.1 * i) * 1e3 for i in range(11)]
    got = stats.open_loop_metrics(res, "p2p", ["p2p_p50_ms", "p2p_p90_ms"])
    assert got["p2p_p50_ms"] == pytest.approx(lat[5])
    assert got["p2p_p90_ms"] == pytest.approx(np.percentile(lat, 90))
    assert stats.lateness(res)["late_max_ms"] == pytest.approx(500.0)
    with pytest.raises(ValueError):
        stats.open_loop_metrics(res, "ssd", ["p2p_p50_ms"])


def test_unanswered_requests_are_failures_not_latencies():
    rows = [(0.0, 0.0, 1.0), (0.0, 0.0, None)]
    res = LoopResult(0.0, 1.0, [Answered(i, *r) for i, r in enumerate(rows)])
    assert res.failed == 1
    assert stats.open_loop_metrics(res, "p2p", ["p2p_p50_ms"]) == {
        "p2p_p50_ms": 1000.0}


def test_spread_is_the_interquartile_distance_over_the_median():
    assert stats.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    # statistics.quantiles (exclusive): 9.75, 10, 15.75
    assert stats.spread([9, 10, 10, 10, 11, 30]) == pytest.approx(0.6)


def test_open_schedule_keeps_the_count_and_changes_the_order():
    degree = np.arange(1, 101)
    mix = {"loop": "open", "mode": "p2p", "rate_per_s": 25.0,
           "endpoints": "degree"}
    a = traffic.open_schedule(mix, degree, 3_000_000_001, 4.0)
    b = traffic.open_schedule(mix, degree, 3_000_000_002, 4.0)
    again = traffic.open_schedule(mix, degree, 3_000_000_001, 4.0)
    assert len(a.due) == len(b.due) == 100
    assert np.array_equal(a.requests, again.requests)
    assert not np.array_equal(a.requests, b.requests)
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 4
    assert np.all(a.requests[:, 0] != a.requests[:, 1])


# -------------------------------------------------------------- tracing
def _trace(ops, modules=(), host=()):
    return {"device": [{"plane": "/device:TPU:0", "ops": list(ops),
                        "modules": list(modules)}],
            "host": [["bench.window", 100, 1000]] + list(host)}


def test_reduction_on_a_synthetic_window():
    ops = [["%a", 50, 100],            # clipped to 100..150
           ["%relax_bucketed.1 [tpu_custom_call]", 200, 100],
           ["%b", 250, 100],           # overlaps the kernel
           ["%relax_bucketed.2 [tpu_custom_call]", 800, 100]]
    host = [["PjitFunction(_ssd_impl)", 120, 10],
            ["CommonPjRtBuffer::ToLiteral", 400, 300]]
    r = tracing.reduce(_trace(ops, [["jit__unknown(7)", 150, 800]], host))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((50 + 150 + 100) * 1e-9)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["CommonPjRtBuffer::ToLiteral", pytest.approx(450e-9)]
    assert r["programs"] == {"_ssd_impl": {"count": 1,
                                           "seconds": pytest.approx(8e-7)}}
    assert tracing.program_time(r, "_ssd_impl")[0] == 1
    assert tracing.program_time(r, "_p2p_impl") is None


def test_op_names_keep_the_instruction_and_custom_call_target():
    hlo = ('%relax_bucketed.3 = f32[32,14720]{1,0} custom-call(f32[32] %a)'
           ', custom_call_target="tpu_custom_call", operand_layout=x')
    assert tracing.op_name(hlo) == "%relax_bucketed.3 [tpu_custom_call]"
    assert tracing.op_name("%fusion.5 = f32[3] fusion(%x)") == "%fusion.5"


def test_reduction_of_a_recorded_chip_trace():
    """Five P2P batches of the social cell traced on a TPU v5e
    (``bench/testdata``): the idle share and kernel time recorded there,
    and the same busy time from a 10 ns timeline of its events."""
    import json
    import os

    path = os.path.join(os.path.dirname(tracing.__file__), "..",
                        "testdata", "trace_social-pa-24k.ic13-open.json")
    with open(path) as f:
        trace = json.load(f)
    r = tracing.reduce(trace)
    assert r["busy_s"] == pytest.approx(0.757585679)
    assert r["window_s"] == pytest.approx(0.804571176)
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(
        5.8399, abs=1e-3)
    assert r["ops"]["%relax_bucketed.3 [tpu_custom_call]"] == {
        "count": 20, "seconds": pytest.approx(0.006785378)}
    assert r["programs"] == {"_p2p_impl": {
        "count": 5, "seconds": pytest.approx(0.757585876)}}

    lo, hi = tracing.window(trace)
    busy = np.zeros((hi - lo) // 10 + 1, bool)
    for _, s, d in trace["device"][0]["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            busy[(a - lo) // 10:(b - lo + 9) // 10] = True
    assert r["busy_s"] == pytest.approx(busy.sum() * 1e-8, rel=1e-3)
    assert len(r["breakdown"]["device_ops"]) == 10
    assert len(r["breakdown"]["idle_gaps"]) == 10


# ----------------------------------------------------- least work, peaks
def test_least_bytes_never_exceed_what_todays_layout_moves():
    """At the side-256 plan widths (S=32, M=36,904, K=16), even with
    every slot a real arc and every row its own destination."""
    s, m, k = 32, 36904, 16
    least = work.least_work(arcs=m * k, destinations=m, rows=s)
    assert least.nbytes <= work.layout_bytes(s, m, k)
    assert work.least_work(0, 0, s) == work.Work(0.0, 0.0)


def test_plan_least_work_counts_real_arcs_only():
    class Plan:
        dst = np.array([[0, 1, 1], [2, 3, 3]])
        w = np.array([[[1.0, np.inf], [2.0, 3.0], [4.0, np.inf]],
                      [[1.0, 1.0], [np.inf, np.inf], [5.0, 6.0]]])
        row_valid = np.array([[True, True, True], [True, True, False]])
        level_mask = np.array([True, False])

    got = work.plan_least_work(Plan, rows=4)
    assert got == work.least_work(arcs=4, destinations=2, rows=4)


def test_peak_table_refuses_an_unknown_device():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
    assert peaks.least_seconds(197e12, 0, "TPU v5 lite") == pytest.approx(1)
    assert peaks.least_seconds(0, 819e9, "TPU v5 lite") == pytest.approx(1)


# ------------------------------------------------- generators, reference
def _grid(side, seed, **kw):
    return graphs.generate({"kind": "grid_road", "side": side,
                            "seed": seed, **kw})


def _arcs_digest(arcs) -> str:
    h = hashlib.sha256(np.int64(arcs.n).tobytes())
    for a, dtype in ((arcs.src, "<i8"), (arcs.dst, "<i8"), (arcs.w, "<f8")):
        h.update(np.ascontiguousarray(a, dtype=dtype).tobytes())
    return h.hexdigest()


def _config(name):
    entry = next(c for c in spec.load_benchmark(ROOT)["configs"]
                 if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


#: SHA-256 over ``n``, ``src``, ``dst`` and ``w`` of each configuration's
#: full-size arcs, as the generators made them when they lived in
#: ``yardstick/graphs.py``: moving a generator must not move its data.
PINNED_ARCS = {
    "road-grid-g256":
        "dd9e139bbc43ce850f93b858381edbc25755d1fbc3eeb03d99c20fd30b26b7fa",
    "social-pa-24k":
        "6aadff9b6e59ce61d673d44261274da20fff20056b9dd69e8644504cb22aff0d",
}


@pytest.mark.parametrize("config", sorted(PINNED_ARCS))
def test_full_size_arcs_keep_their_pinned_digest(config):
    arcs = graphs.generate(_config(config)["graph"])
    assert _arcs_digest(arcs) == PINNED_ARCS[config]


#: The controls each configuration's stated guarantees give.
PINNED_CONTROLS = {"road-grid-g256": {"bfloat16"},
                   "social-pa-24k": {"hop_capped"}}


@pytest.mark.parametrize("config", sorted(PINNED_CONTROLS))
def test_each_configuration_keeps_its_controls(config):
    conf = _config(config)
    arcs = graphs.generate({**conf["graph"], **conf["cpu_test"]["graph"]})
    got = reference.controls_for(conf["guarantees"], arcs, farthest=3.0)
    assert set(got) == PINNED_CONTROLS[config]


def test_generators_repeat_the_repositorys_own():
    from repro.core import (from_edges, grid_road_graph, power_law_digraph,
                            symmetrize)

    social = {"kind": "power_law_social", "persons": 120, "m_per_node": 5,
              "seed": 4}
    for mine, theirs in (
            (_grid(9, 4), grid_road_graph(9, seed=4)),
            (graphs.generate(social),
             symmetrize(power_law_digraph(120, 5, seed=4)))):
        g = from_edges(mine.n, mine.src, mine.dst, mine.w)
        for a, b in zip(g.edge_list(), theirs.edge_list()):
            assert np.array_equal(a, b)


@pytest.mark.parametrize("kind", ["no_such_kind", "../x", "grid_road/x"])
def test_an_unknown_or_escaping_graph_kind_is_refused(kind):
    with pytest.raises(spec.SpecError):
        graphs.generate({"kind": kind, "side": 4, "seed": 1})


#: Bodies of a generator that returns malformed arcs.
BAD_ARCS = {
    "negative_weight": "Arcs(3, src, dst, np.array([1.0, -1.0]))",
    "zero_weight": "Arcs(3, src, dst, np.array([1.0, 0.0]))",
    "nan_weight": "Arcs(3, src, dst, np.array([1.0, np.nan]))",
    "endpoint_out_of_range": "Arcs(3, src, np.array([1, 3]), w)",
    "negative_endpoint": "Arcs(3, np.array([0, -1]), dst, w)",
    "int32_endpoints": "Arcs(3, src.astype(np.int32), dst, w)",
    "integer_weights": "Arcs(3, src, dst, w.astype(np.int64))",
    "lengths_differ": "Arcs(3, src, dst, w[:1])",
    "no_nodes": "Arcs(0, src[:0], dst[:0], w[:0])",
    "not_arcs": "(3, src, dst, w)",
}


@pytest.mark.parametrize("fault", sorted(BAD_ARCS))
def test_a_generator_that_returns_malformed_arcs_is_refused(tmp_path,
                                                            fault):
    root = copy_benchmark(tmp_path)
    with open(os.path.join(root, "bench", "graphs", "bad.py"), "w") as f:
        f.write("import numpy as np\n"
                "from yardstick.graphs import Arcs\n\n\n"
                "def arcs():\n"
                "    src, dst = np.array([0, 1]), np.array([1, 2])\n"
                "    w = np.array([1.0, 2.0])\n"
                f"    return {BAD_ARCS[fault]}\n")
    with pytest.raises(spec.SpecError):
        graphs.generate({"kind": "bad"}, root)


def test_reference_agrees_with_the_engine_on_a_side_16_grid():
    from repro.core import QueryEngine, from_edges
    from repro.launch.serve import build_served_index

    arcs = _grid(16, 5)
    ix, _ = build_served_index(from_edges(arcs.n, arcs.src, arcs.dst,
                                          arcs.w))
    eng = QueryEngine(ix, use_pallas=False)
    ref = reference.Reference(arcs)
    sources = np.array([0, 17, 100, 255], np.int32)
    got = eng.ssd(sources)
    for i, s in enumerate(sources.tolist()):
        assert np.array_equal(got[i].astype(np.float64), ref.ssd(s))
    pairs = np.array([[3, 250], [77, 78], [200, 9]], np.int32)
    want = [ref.p2p(int(s), int(t)) for s, t in pairs]
    assert eng.p2p(pairs[:, 0], pairs[:, 1]).tolist() == want


def test_controls_break_the_guarantee_they_name():
    road = _grid(100, 6)
    exact = reference.Reference(road)
    bf16 = reference.control(road, "bfloat16")
    far = exact.ssd(0)
    assert max(far) > 256 and bf16.ssd(0) != far
    assert reference.control(road, "hop_capped", hops=1000).ssd(0) == far
    capped = reference.control(road, "hop_capped", hops=5).ssd(0)
    assert math.isinf(capped[-1]) and capped[1] == far[1]


def test_the_reversed_control_walks_every_arc_backwards():
    # 0 -> 1 -> 2 and 0 -> 2 the long way; nothing leads back to 0
    arcs = graphs.Arcs(3, np.array([0, 1, 0]), np.array([1, 2, 2]),
                       np.array([1.0, 2.0, 5.0]))
    assert reference.Reference(arcs).ssd(0) == [0.0, 1.0, 3.0]
    rev = reference.controls_for({"labels": "float32", "arcs": "directed"},
                                 arcs, farthest=None)
    assert set(rev) == {"bfloat16", "reversed"}
    assert rev["reversed"].ssd(0) == [0.0, math.inf, math.inf]
    assert rev["reversed"].p2p(2, 0) == 3.0
    assert rev["bfloat16"].ssd(0) == [0.0, 1.0, 3.0]


@pytest.mark.parametrize("guarantees", [
    {"labels": "float16"}, {"arcs": "undirected"}, {"durability": "fsync"},
    {"answers": "exact"}])
def test_a_guarantee_with_no_control_is_refused(guarantees):
    """A value no control breaks, and weighted answers with nothing
    stated to break, raise rather than go unguarded."""
    with pytest.raises(ValueError):
        reference.controls_for(guarantees, _grid(4, 1), farthest=5.0)


def test_a_pool_seed_gives_every_seed_the_same_batches():
    """A closed loop's batches are fixed; the seed orders each one."""
    mix = {"loop": "closed", "mode": "ssd", "clients": 4,
           "endpoints": "uniform"}
    degree = np.ones(1000)

    def first(seed, n=12):
        stream = traffic.closed_stream(mix, degree, seed)
        return np.array([next(stream) for _ in range(n)]).reshape(-1, 4)

    a, b = first(3_000_000_001), first(3_000_000_002)
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a, axis=1), np.sort(b, axis=1))
    assert len(np.unique(a)) > 4


def test_longest_gap_finds_a_stall():
    rows = [(0.0, 0.0, 0.2), (0.0, 0.0, 0.4), (0.0, 0.0, 3.4),
            (0.0, 0.0, 3.6)]
    gap, at = stats.longest_gap(_answered(rows))
    assert gap == pytest.approx(3.0) and at == pytest.approx(0.4)
