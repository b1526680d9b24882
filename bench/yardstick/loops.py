"""Closed- and open-loop drivers of a ``QueryServer``.

Both run on one asyncio loop with the server, as its clients would, and
time every request on one clock (``time.perf_counter``).

* Closed loop: ``clients`` callers, each sending its next request once
  answered.  Callers stop sending once a batch completes past the
  window's end, so the window runs from the first dispatch to the end of
  the last batch dispatched inside ``seconds``, and every batch in it is
  whole.
* Open loop: every request has an absolute due time fixed before the
  window opens.  The generator sends all requests that are due whenever
  it holds the loop, so one that falls due while a batch blocks the loop
  is sent late, and its latency, taken from the due time, counts that
  wait.  Requests still in flight at the window's end are awaited and
  counted.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Iterator, List, Optional

import numpy as np


@dataclasses.dataclass
class Answered:
    """One request and what came back."""

    request: object                 # source, or (source, target)
    due: float                      # seconds on the loop clock
    sent: float
    done: Optional[float] = None    # None: never answered
    answer: object = None           # [n] row (ssd) or a scalar (p2p)
    error: Optional[str] = None
    cached: bool = False


@dataclasses.dataclass
class LoopResult:
    start: float
    end: float                      # last answer
    answered: List[Answered]

    @property
    def failed(self) -> int:
        return sum(a.done is None for a in self.answered)


async def _ask(server, mode: str, req, rec: Answered) -> None:
    try:
        if mode == "p2p":
            res = await server.submit(int(req[0]), int(req[1]), mode=mode)
        else:
            res = await server.submit(int(req), mode=mode)
    except Exception as exc:       # a failed request is counted, not fatal
        rec.error = f"{type(exc).__name__}: {exc}"
        return
    rec.done = time.perf_counter()
    rec.answer = res.dist
    rec.cached = res.cached


def _key(mode: str, req):
    return (int(req[0]), int(req[1])) if mode == "p2p" else int(req)


async def _closed(server, mode: str, stream: Iterator, clients: int,
                  seconds: float) -> LoopResult:
    start = time.perf_counter()
    stop_at = start + seconds
    answered: List[Answered] = []
    # When each batch count was first seen: every caller woken by one
    # batch takes the same decision, so no batch is left partial.
    seen = {"batches": server.stats.batches, "at": start}

    async def caller() -> None:
        rec = None
        while True:
            if server.stats.batches != seen["batches"]:
                seen["batches"] = server.stats.batches
                seen["at"] = time.perf_counter()
            # An answer from the result cache ran no batch: its caller
            # goes by the clock, and lets the others run.
            cached = rec is not None and rec.cached
            if (time.perf_counter() if cached else seen["at"]) >= stop_at:
                return
            if cached:
                await asyncio.sleep(0)
            req = next(stream)
            now = time.perf_counter()
            rec = Answered(_key(mode, req), now, now)
            answered.append(rec)
            await _ask(server, mode, req, rec)
            if rec.done is None:
                return

    await asyncio.gather(*(caller() for _ in range(clients)))
    end = max((a.done for a in answered if a.done is not None),
              default=start)
    return LoopResult(start, end, answered)


async def _open(server, mode: str, due: np.ndarray,
                requests: np.ndarray) -> LoopResult:
    start = time.perf_counter()
    answered: List[Answered] = []
    tasks = []
    i, count = 0, len(due)
    while i < count:
        now = time.perf_counter()
        while i < count and start + due[i] <= now:
            rec = Answered(_key(mode, requests[i]), start + float(due[i]),
                           now)
            answered.append(rec)
            tasks.append(asyncio.create_task(
                _ask(server, mode, requests[i], rec)))
            i += 1
        if i < count:
            await asyncio.sleep(max(0.0, start + due[i]
                                    - time.perf_counter()))
    await asyncio.gather(*tasks)
    await server.drain()
    end = max((a.done for a in answered if a.done is not None),
              default=start)
    return LoopResult(start, end, answered)


def closed_loop(server, mode: str, stream: Iterator, clients: int,
                seconds: float) -> LoopResult:
    return asyncio.run(_closed(server, mode, stream, clients, seconds))


def open_loop(server, mode: str, due: np.ndarray,
              requests: np.ndarray) -> LoopResult:
    return asyncio.run(_open(server, mode, due, requests))
