"""The per-layer readers of the program's phase spans
(``bench/metrics/<name>.py`` over ``yardstick/phases.py``), each on
hand-built readings: the number it gives where the spans are there, and
nothing where they are not, as with a program that records none."""
import pytest
from benchsupport import ROOT
from yardstick import spec
from yardstick.cell import Readings

ROAD, SOCIAL = "road-grid-g256.ssd-closed", "social-pa-24k.ic13-open"
MS = 1_000_000   # ns


def _span(name, t0, t1, **args):
    return {"name": name, "tname": "MainThread", "t0": t0, "t1": t1,
            "args": args}


def _batch(mode, batch, at, fill, rounds, row_rounds, resolve_ms=(1,)):
    """One served batch's spans: dispatch 2 ms, wait 100 ms, readback
    ``batch`` ms, then each ``server.resolve``."""
    tags = dict(batch=batch, mode=mode, fill=fill, core_rounds=rounds,
                core_row_rounds=row_rounds)
    out = [_span(f"query.{mode}", at, at + 200 * MS, batch=batch,
                 fill=fill),
           _span("engine.dispatch", at, at + 2 * MS, **tags),
           _span("engine.wait", at + 2 * MS, at + 102 * MS, **tags),
           _span("engine.readback", at + 102 * MS,
                 at + (102 + batch) * MS, **tags)]
    t = at + 150 * MS
    for ms in resolve_ms:
        out.append(_span("server.resolve", t, t + ms * MS, batch=batch))
        t += 10 * MS
    return out


def _readings(cell, spans):
    return Readings(spec.resolve(cell, ROOT), "TPU v5 lite", None, spans,
                    batches=2, batch_size=32, index=None)


def _spans(mode):
    warm = [_span(n, 0, 9000 * MS, mode=mode, fill=32, core_rounds=50,
                  core_row_rounds=1600) for n in
            ("engine.dispatch", "engine.wait", "engine.readback")]
    return (warm
            + _batch(mode, 1, 10_000 * MS, 32, 10, 160, resolve_ms=(1, 2))
            + _batch(mode, 2, 11_000 * MS, 16, 20, 80, resolve_ms=(3,)))


CASES = [
    # (metric, cell, value): the warm-up call (no batch id) left out
    ("core_rounds.ssd", ROAD, 15.0),
    ("core_rounds.p2p", SOCIAL, 15.0),
    # 1 - (160 + 80) / (32 * 10 + 16 * 20)
    ("core_settled_share.ssd", ROAD, 100.0 * (1 - 240 / 640)),
    ("readback_ms.ssd", ROAD, 1.5),
    ("readback_ms.p2p", SOCIAL, 1.5),
    # batch 1: 2 + 1 + (1 + 2); batch 2: 2 + 2 + 3
    ("server_host_ms.p2p", SOCIAL, 6.5),
]


@pytest.mark.parametrize("metric, cell, value", CASES)
def test_reader_gives_its_number(metric, cell, value):
    mode = spec.resolve(cell, ROOT).traffic["mode"]
    read = spec.load_reader(metric, ROOT)
    assert read(_readings(cell, _spans(mode))) == pytest.approx(value)
    other = "p2p" if mode == "ssd" else "ssd"
    assert read(_readings(cell, _spans(other))) is None


@pytest.mark.parametrize("metric, cell, value", CASES)
def test_reader_finds_nothing_without_phase_spans(metric, cell, value):
    """A program without phase spans (its batches traced only as
    ``query.<mode>`` and ``coalesce.wait``) reads as no value."""
    mode = spec.resolve(cell, ROOT).traffic["mode"]
    spans = [_span(f"query.{mode}", 0, 5 * MS, batch=1, fill=32),
             _span("jit.dispatch", 0, 5 * MS, mode=mode),
             _span("coalesce.wait", 0, 2 * MS, waiters=32)]
    assert spec.load_reader(metric, ROOT)(_readings(cell, spans)) is None


def test_core_counts_are_absent_where_the_core_search_counts_nothing():
    spans = _spans("ssd")
    for s in spans:
        s["args"].pop("core_rounds", None)
        s["args"].pop("core_row_rounds", None)
    for metric in ("core_rounds.ssd", "core_settled_share.ssd"):
        assert spec.load_reader(metric, ROOT)(
            _readings(ROAD, spans)) is None
    assert spec.load_reader("readback_ms.ssd", ROOT)(
        _readings(ROAD, spans)) == pytest.approx(1.5)
