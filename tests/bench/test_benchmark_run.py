"""Whole runs of the harness on the CPU, at a size the interpreter holds.

The chip check is skipped (``require_chip=False``); everything after it
runs as on the chip: set-up, the cell's own loop, and the comparison
that decides ``correct``.  A sound program comes out correct, and each
fault a served query can have, planted in the timed path, comes out
not correct.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest
from benchsupport import ROOT, copy_benchmark, tiny_benchmark
from yardstick import cell as cellmod
from yardstick import spec, traffic
from yardstick.reference import Reference

import control

from repro.core.query import QueryEngine

CELLS = [w["name"] for w in spec.load_benchmark(ROOT)["workloads"]]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_benchmark(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")


def _run(root, cell, trace=0):
    args = cellmod.parse(["--workload", cell, "--seed", "3000000019",
                          "--seconds", "0.5", "--trace", str(trace)])
    return cellmod.run(args, time.perf_counter(), root=root,
                       require_chip=False)


def _altered(orig):
    """Every answer of the batch off by one in one place."""
    def fn(self, *ends):
        out = np.array(orig(self, *ends))
        if out.ndim == 2:
            out[:, -1] += 1
        else:
            out += 1
        return out
    return fn


def _half(orig):
    """Only the first half of the batch computed; its answers stand in
    for the second half's."""
    def fn(self, *ends):
        h = len(ends[0]) // 2
        return orig(self, *(np.concatenate([e[:h], e[:h]]) for e in ends))
    return fn


FAULTS = {
    "answer_altered": lambda mp: [
        mp.setattr(QueryEngine, m, _altered(getattr(QueryEngine, m)))
        for m in ("ssd", "p2p")],
    "half_batch_left_out": lambda mp: [
        mp.setattr(QueryEngine, m, _half(getattr(QueryEngine, m)))
        for m in ("ssd", "p2p")],
    "core_search_state_unchanged": lambda mp: mp.setattr(
        QueryEngine, "_core_update", lambda self, dist, core, mode: dist),
}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(tiny, cell):
    out = _run(tiny, cell)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    c = spec.resolve(cell, tiny)
    assert set(out["metrics"]) == {m["name"] for m in c.end_to_end}
    assert list(out)[-1] == "compared"
    assert out["compared"] == {"wrong": {"value": 0, "limit": 0},
                               "unanswered": {"value": 0, "limit": 0}}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_fault_in_the_timed_path_is_not_correct(tiny, cell, fault,
                                                   monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run(tiny, cell)
    assert out["correct"] is False
    assert out["compared"]["wrong"]["value"] > 0


#: The largest whole number below which a precision holds every integer
#: exactly: a control rounding to it cannot fail on shorter distances.
EXACT_INTEGERS = {"bfloat16": 2 ** 8}


def assert_controls_fail(row, farthest):
    """Every control in ``row`` fails the comparison, save a precision
    control where no sampled distance passes that precision's exact
    integers (it then reads ``wrong`` 0); at least one fails."""
    assert row["program"] == {"wrong": 0, "unanswered": 0}
    assert row["controls"]
    for name, numbers in row["controls"].items():
        if farthest <= EXACT_INTEGERS.get(name, -1):
            assert numbers["wrong"] == 0, name
        else:
            assert numbers["wrong"] > 0, name
    assert any(n["wrong"] > 0 for n in row["controls"].values())


def sampled_farthest(cell, answered, truth, seed):
    """The largest finite reference distance among the answers a run
    samples."""
    from yardstick import check

    mode = cell.traffic["mode"]
    picks = check.sample(answered,
                         int(cell.config["check"][f"{mode}_answers"]), seed)
    return max(control._farthest(mode, answered[i].request, truth)
               for i in picks)


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_in_the_programs_place_is_not_correct(tiny, cell):
    """Each of the configuration's controls (``bench/control.py``), put
    in the program's place for the answers a run samples, fails the
    comparison that the program's own answers pass."""
    c = spec.resolve(cell, tiny)
    mix = traffic.validate(dict(c.traffic))
    served = cellmod.setup(c, mix["mode"], trace=False)
    res = cellmod.drive(served, mix, 3000000029, 0.5)
    truth = Reference(served.arcs)
    row = control.readings(c, served.arcs, truth, res.answered, 3000000029)
    assert_controls_fail(row, sampled_farthest(c, res.answered, truth,
                                               3000000029))


def test_result_line_is_the_last_line_and_limits_follow_on_stderr(
        tiny, capsys, monkeypatch):
    monkeypatch.setattr(cellmod, "require_chips",
                        lambda jax, chips: jax.devices())
    assert cellmod.main(["--workload", CELLS[-1], "--seed", "5",
                         "--seconds", "0.3", "--trace", "0"],
                        root=tiny) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "compared"
    assert err.strip().splitlines()[-2:] == [
        "compared wrong 0 limit 0", "compared unanswered 0 limit 0"]


def _bench_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_non_zero_without_a_tpu():
    proc = _bench_cmd(ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_run_exits_non_zero_with_only_the_benchmarks_files(tmp_path):
    proc = _bench_cmd(copy_benchmark(tmp_path))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
