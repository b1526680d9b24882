"""Capture a profiler trace of the window and reduce it to numbers.

``capture`` runs the window under ``jax.profiler`` with the Python
tracer off.  ``load`` turns the ``.xplane.pb`` it wrote into a small
plain form (a recorded one, as JSON, is the reduction's test data):

    {"device": [{"plane": str, "ops": [[name, start_ns, dur_ns], ...],
                 "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's ``XLA Ops`` line (each compiled operation,
Pallas kernels among them, named by ``op_name``), ``modules`` its ``XLA
Modules`` line (one event per run of a compiled program), and ``host``
every host event, ``TraceAnnotation`` spans and the ``PjitFunction(<f>)``
dispatch of each jitted call among them.  All share the profiler's
clock.  A program's device name (``jit__unknown(<fingerprint>)`` for a
jitted ``functools.partial``) does not say which function it runs, so
each program is named by the host dispatch that launched its first run.

``reduce`` gives, inside the window the benchmark marked with the
``bench.window`` annotation: the seconds in which some operation ran
(the union of op intervals, averaged over the chips), the window's
length, device time per op name and per program, and the longest idle
gaps, each named by the host event that covers most of it.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterator, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PJIT = "PjitFunction("


@contextlib.contextmanager
def capture() -> Iterator[dict]:
    """Trace the ``with`` body; on exit ``out["trace"]`` holds the plain
    form, and the raw trace is deleted."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    out: dict = {}
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield out
    finally:
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True)
            if paths:
                out["trace"] = load(max(paths, key=os.path.getmtime))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(hlo: str) -> str:
    """An ``XLA Ops`` event's name is its HLO instruction's text; keep
    the instruction's name, and a custom call's target after it:
    ``%relax_bucketed.3 [tpu_custom_call]``."""
    name = hlo.split(" = ", 1)[0].strip()
    target = _TARGET.search(hlo)
    return f"{name} [{target.group(1)}]" if target else name


def _events(line, rename=str) -> List[list]:
    return [[rename(str(e.name)), int(e.start_ns), int(e.duration_ns)]
            for e in line.events]


def load(path: str) -> dict:
    """The plain form of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            device.append({
                "plane": plane.name,
                "ops": _events(lines[OPS_LINE], op_name),
                "modules": (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else [])})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += _events(ln)
    return {"device": device, "host": host}


def window(trace: dict) -> Tuple[int, int]:
    """``(start_ns, end_ns)`` of the ``bench.window`` annotation."""
    marks = [e for e in trace["host"] if e[0] == WINDOW]
    if not marks:
        raise ValueError("trace has no bench.window annotation")
    name, start, dur = max(marks, key=lambda e: e[2])
    return start, start + dur


def _clip(events, lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _by_name(events, lo: int, hi: int) -> Dict[str, dict]:
    """Per name: count and device seconds of the events that start in
    the window."""
    out: Dict[str, dict] = {}
    for name, s, d in events:
        if lo <= s < hi:
            row = out.setdefault(name, {"count": 0, "seconds": 0.0})
            row["count"] += 1
            row["seconds"] += d / 1e9
    return out


def _host_cover(host, a: int, b: int) -> str:
    """The host event that covers most of ``[a, b)``, the window itself
    left out; ``"none"`` where no host event overlaps it."""
    best, best_ns = "none", 0
    for name, s, d in host:
        if name == WINDOW:
            continue
        ov = min(s + d, b) - max(s, a)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def _program_names(trace: dict) -> Dict[str, str]:
    """Device program name -> the jitted function whose host dispatch
    (``PjitFunction(<f>)``) most closely precedes its first run."""
    dispatch = sorted((s, name[len(PJIT):-1]) for name, s, _ in trace["host"]
                      if name.startswith(PJIT) and name.endswith(")"))
    starts = [s for s, _ in dispatch]
    out: Dict[str, str] = {}
    for chip in trace["device"]:
        for name, s, _ in sorted(chip["modules"], key=lambda e: e[1]):
            i = bisect.bisect_right(starts, s) - 1
            if name not in out and i >= 0:
                out[name] = dispatch[i][1]
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """The window's device numbers (see the module docstring)."""
    lo, hi = window(trace)
    chips = trace["device"]
    if not chips:
        raise ValueError("trace has no device plane with XLA Ops")
    busy, ops, modules, gaps = 0.0, {}, {}, []
    names = _program_names(trace)
    for chip in chips:
        merged = _union(_clip(chip["ops"], lo, hi))
        busy += sum(b - a for a, b in merged) / 1e9
        for table, events in ((ops, chip["ops"]),
                              (modules, chip["modules"])):
            for name, row in _by_name(events, lo, hi).items():
                acc = table.setdefault(name, {"count": 0, "seconds": 0.0})
                acc["count"] += row["count"]
                acc["seconds"] += row["seconds"]
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    programs: Dict[str, dict] = {}
    for module, row in modules.items():
        acc = programs.setdefault(names.get(module, module),
                                  {"count": 0, "seconds": 0.0})
        acc["count"] += row["count"]
        acc["seconds"] += row["seconds"]
    gaps.sort(key=lambda g: g[0] - g[1])
    gap_rows = [[_host_cover(trace["host"], a, b), (b - a) / 1e9]
                for a, b in gaps[:top]]
    op_rows = sorted(([n, r["seconds"]] for n, r in ops.items()),
                     key=lambda r: -r[1])[:top]
    return {"busy_s": busy / len(chips), "window_s": (hi - lo) / 1e9,
            "ops": ops, "programs": programs,
            "breakdown": {"device_ops": op_rows, "idle_gaps": gap_rows}}


def program_time(reduced: dict, function: str
                 ) -> Optional[Tuple[int, float]]:
    """``(runs, device seconds)`` of the programs of the jitted
    ``function``; ``None`` where none ran."""
    rows = [r for n, r in reduced["programs"].items() if n == function]
    if not rows:
        return None
    return (sum(r["count"] for r in rows),
            sum(r["seconds"] for r in rows))
