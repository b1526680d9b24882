"""Percentile and window arithmetic, and the spread that bounds are set from.

Percentiles are exact, over every request: linear interpolation between
the two nearest ranks of the sorted sample (numpy's default).  None is
taken from histogram buckets or from per-chunk medians.
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: ``<mode>_p<q>_ms``: the q-th percentile of that mode's latency.
PERCENTILE = re.compile(r"^([a-z0-9]+)_p(\d{1,2})_ms$")


def percentile(values: Sequence[float], q: float) -> float:
    if not len(values):
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def open_loop_metrics(result, mode: str, names: List[str]) -> Dict[str, float]:
    """The open-loop metrics ``names`` asks for: latency percentiles from
    each request's due time to its answer, over all requests.  A request
    never answered has no latency; it is counted under ``failed``."""
    lat_ms = [(a.done - a.due) * 1e3 for a in result.answered
              if a.done is not None]
    out = {}
    for name in names:
        m = PERCENTILE.match(name)
        if m is None or m.group(1) != mode:
            raise ValueError(f"an open {mode} loop cannot give {name!r}")
        out[name] = percentile(lat_ms, float(m.group(2)))
    return out


def closed_loop_metrics(result, names: List[str]) -> Dict[str, float]:
    """Answers per second over the closed-loop window, which ends with
    the last batch dispatched inside it, so it holds whole batches."""
    span = result.end - result.start
    answered = sum(a.done is not None for a in result.answered)
    out = {}
    for name in names:
        if not name.endswith("_per_s"):
            raise ValueError(f"a closed loop cannot give {name!r}")
        out[name] = answered / span
    return out


def lateness(result) -> Dict[str, float]:
    """How late the generator sent requests, in ms (open loop)."""
    late = np.asarray([(a.sent - a.due) * 1e3 for a in result.answered])
    if not late.size:
        return {"late_mean_ms": 0.0, "late_p90_ms": 0.0, "late_max_ms": 0.0}
    return {"late_mean_ms": float(late.mean()),
            "late_p90_ms": percentile(late, 90),
            "late_max_ms": float(late.max())}


def longest_gap(result) -> Tuple[float, float]:
    """The longest time between consecutive answers (or from the
    window's start to the first), in seconds, and when it began, in
    seconds into the window: a stall of the loop or the device shows
    here."""
    done = sorted(a.done for a in result.answered if a.done is not None)
    edges = np.asarray([result.start] + done)
    if edges.size < 2:
        return 0.0, 0.0
    gaps = np.diff(edges)
    i = int(gaps.argmax())
    return float(gaps[i]), float(edges[i] - result.start)


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, as a share: the spread
    that a metric's bound is set from (``statistics.quantiles``)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
