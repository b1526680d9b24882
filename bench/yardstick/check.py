"""The comparison that decides ``correct``.

After the window closes, a sample of the answers the timed server
returned, drawn from the seed, is compared with the reference on the
same graph.  Distances are exact integers on both sides, so an answer is
right only if it equals the reference exactly (every entry of an SSD
row).  Two numbers are compared, each against its limit:

* ``wrong``: sampled answers that differ from the reference;
* ``unanswered``: requests that failed or never came back.

Both limits are 0: the comparison is exact (PERF.md gives the readings
of sound runs and of the controls behind them).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

LIMITS = {"wrong": 0, "unanswered": 0}


@dataclasses.dataclass
class Comparison:
    checked: int
    numbers: Dict[str, int]

    @property
    def correct(self) -> bool:
        return all(v <= LIMITS[k] for k, v in self.numbers.items())

    def report(self) -> Dict[str, dict]:
        """``{name: {"value", "limit"}}``, the result line's last key."""
        return {k: {"value": v, "limit": LIMITS[k]}
                for k, v in self.numbers.items()}


def sample(answered, k: int, seed: int) -> List[int]:
    """Indices of up to ``k`` answered requests, drawn from the seed."""
    idx = [i for i, a in enumerate(answered) if a.done is not None]
    if len(idx) <= k:
        return idx
    rng = np.random.default_rng([seed, 3])
    return sorted(rng.choice(idx, size=k, replace=False).tolist())


def is_right(mode: str, request, answer, reference) -> bool:
    if mode == "p2p":
        s, t = request
        return float(answer) == reference.p2p(s, t)
    want = np.asarray(reference.ssd(request), dtype=np.float64)
    got = np.asarray(answer, dtype=np.float64)
    return got.shape == want.shape and bool(np.array_equal(got, want))


def compare(mode: str, answered, reference, k: int,
            seed: int) -> Comparison:
    picks = sample(answered, k, seed)
    wrong = sum(not is_right(mode, answered[i].request,
                             answered[i].answer, reference)
                for i in picks)
    unanswered = sum(a.done is None for a in answered)
    return Comparison(len(picks), {"wrong": wrong,
                                   "unanswered": unanswered})
