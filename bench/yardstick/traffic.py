"""The one traffic generator: a mix's parameters plus a seed give requests.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when answered) or ``"open"`` (requests due on a schedule
  fixed in advance, at ``rate_per_s``);
* ``mode``: the ``QueryServer`` mode, ``"ssd"`` or ``"p2p"``;
* ``endpoints``: ``"uniform"`` over the nodes, or ``"degree"``, each
  endpoint drawn in proportion to its degree.

A closed loop sends the same batches in every run, drawn once from a
fixed seed; ``--seed`` only orders the requests inside each batch.  A
batch's cost follows its hardest request (the bellman core search runs
until every row has settled), so batches drawn anew per seed would make
the seed change the work.

An open loop's schedule is a Poisson process over the window, drawn
given its count: ``round(rate_per_s * seconds)`` requests at sorted
uniform times.  Every seed so gets the same number of requests, and
only their order and spacing change.
"""
from __future__ import annotations

from typing import Iterator, NamedTuple, Optional

import numpy as np

LOOPS = ("closed", "open")
MODES = ("ssd", "p2p")
ENDPOINTS = ("uniform", "degree")


def validate(traffic: dict) -> dict:
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"traffic loop must be one of {LOOPS}")
    if traffic.get("mode") not in MODES:
        raise ValueError(f"traffic mode must be one of {MODES}")
    if traffic.get("endpoints", "uniform") not in ENDPOINTS:
        raise ValueError(f"traffic endpoints must be one of {ENDPOINTS}")
    if traffic["loop"] == "closed" and int(traffic.get("clients", 0)) < 1:
        raise ValueError("a closed loop needs clients >= 1")
    if traffic["loop"] == "open" and not float(
            traffic.get("rate_per_s", 0)) > 0:
        raise ValueError("an open loop needs rate_per_s > 0")
    return traffic


class Schedule(NamedTuple):
    """An open loop's requests: due times (seconds from the window's
    start) and endpoints (``[N]`` sources or ``[N, 2]`` pairs)."""

    due: np.ndarray
    requests: np.ndarray


def _endpoint_p(traffic: dict, degree: np.ndarray) -> Optional[np.ndarray]:
    if traffic.get("endpoints", "uniform") == "uniform":
        return None
    return degree / degree.sum()


def _draw(rng, traffic: dict, degree: np.ndarray, count: int) -> np.ndarray:
    """``count`` requests: sources, or pairs with distinct endpoints."""
    n = degree.shape[0]
    p = _endpoint_p(traffic, degree)
    if traffic["mode"] == "ssd":
        return rng.choice(n, size=count, p=p).astype(np.int32)
    pairs = rng.choice(n, size=(count, 2), p=p)
    same = pairs[:, 0] == pairs[:, 1]
    while same.any():
        pairs[same, 1] = rng.choice(n, size=int(same.sum()), p=p)
        same = pairs[:, 0] == pairs[:, 1]
    return pairs.astype(np.int32)


def open_schedule(traffic: dict, degree: np.ndarray, seed: int,
                  seconds: float) -> Schedule:
    count = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    rng = np.random.default_rng([seed, 1])
    due = np.sort(rng.uniform(0.0, seconds, size=count))
    return Schedule(due, _draw(rng, traffic, degree, count))


#: The closed loops' batches are drawn from this seed, in every run.
BATCHES_SEED = 1


def closed_stream(traffic: dict, degree: np.ndarray,
                  seed: int) -> Iterator[np.ndarray]:
    """A closed loop's requests, one at a time, in the order sent: the
    same batches of ``clients`` requests for every seed, each batch in
    the seed's order."""
    group = int(traffic["clients"])
    rng = np.random.default_rng([BATCHES_SEED, 2])
    order = np.random.default_rng([seed, 4])
    while True:
        yield from order.permutation(_draw(rng, traffic, degree, group))
