"""The yardstick's arithmetic on inputs whose answers are known: window
and percentile rules, the trace reduction, the least-work count and the
peak table, the generators and the reference."""
import math

import numpy as np
import pytest
import benchsupport  # noqa: F401  (puts bench/ on the path)
from yardstick import graphs, peaks, reference, stats, tracing, traffic, work
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
def test_generators_repeat_the_repositorys_own():
    from repro.core import (from_edges, grid_road_graph, power_law_digraph,
                            symmetrize)

    for mine, theirs in (
            (graphs.grid_road(9, seed=4), grid_road_graph(9, seed=4)),
            (graphs.power_law_social(120, 5, seed=4),
             symmetrize(power_law_digraph(120, 5, seed=4)))):
        g = from_edges(mine.n, mine.src, mine.dst, mine.w)
        for a, b in zip(g.edge_list(), theirs.edge_list()):
            assert np.array_equal(a, b)


def test_reference_agrees_with_the_engine_on_a_side_16_grid():
    from repro.core import QueryEngine, from_edges
    from repro.launch.serve import build_served_index

    arcs = graphs.grid_road(16, seed=5)
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
    road = graphs.grid_road(100, seed=6)
    exact = reference.Reference(road)
    bf16 = reference.control(road, "bfloat16")
    far = exact.ssd(0)
    assert max(far) > 256 and bf16.ssd(0) != far
    assert reference.control(road, "hop_capped", hops=1000).ssd(0) == far
    capped = reference.control(road, "hop_capped", hops=5).ssd(0)
    assert math.isinf(capped[-1]) and capped[1] == far[1]


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
