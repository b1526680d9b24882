"""Bodies shared by the per-layer readers of the program's phase spans
(``bench/metrics/<name>.py``), split by the cell's query mode.

In a traced run the server binds each batch's id and fill, and the
engine records three phase spans per public call, each tagged with
them: ``engine.dispatch`` (endpoints to the device, the program
launched), ``engine.wait`` (until the device is done) and
``engine.readback`` (the answer copied to the host and put back in node
order).  A bellman core search adds its counts to those tags:
``core_rounds``, the rounds the search ran (the last one changes
nothing), and ``core_row_rounds``, the sum over the batch's real rows of
the last round in which each row changed.  The server's own host work
after the engine returns is ``server.resolve``.  Spans without a batch
id (the benchmark's own warm-up call) are left out.
"""
from __future__ import annotations

from collections import defaultdict
from typing import List, Optional

HOST_PHASES = ("engine.dispatch", "engine.readback", "server.resolve")


def _batch_spans(r, name: str) -> List[dict]:
    """The cell's ``name`` spans that belong to a served batch of the
    cell's mode."""
    mode = r.cell.traffic["mode"]
    return [s for s in r.spans
            if s["name"] == name and "batch" in s["args"]
            and s["args"].get("mode", mode) == mode]


def _counted(r) -> List[dict]:
    return [s["args"] for s in _batch_spans(r, "engine.readback")
            if "core_rounds" in s["args"]]


def core_rounds(r) -> Optional[float]:
    """Mean rounds of the bellman core search per batch."""
    tags = _counted(r)
    return (sum(t["core_rounds"] for t in tags) / len(tags)
            if tags else None)


def core_settled_share(r) -> Optional[float]:
    """Share of the row-rounds of the core search in which the row had
    already settled, in %: 1 - the rounds rows were still changing over
    every real row times the rounds its batch ran."""
    tags = _counted(r)
    ran = sum(t["fill"] * t["core_rounds"] for t in tags)
    if not ran:
        return None
    return 100.0 * (1.0 - sum(t["core_row_rounds"] for t in tags) / ran)


def readback_ms(r) -> Optional[float]:
    """Mean ``engine.readback`` span per batch, in ms."""
    spans = _batch_spans(r, "engine.readback")
    return (sum(s["t1"] - s["t0"] for s in spans) / len(spans) / 1e6
            if spans else None)


def server_host_ms(r) -> Optional[float]:
    """Mean host time per batch outside the device wait, in ms: its
    ``engine.dispatch``, ``engine.readback`` and ``server.resolve``
    spans summed."""
    per_batch = defaultdict(int)
    for name in HOST_PHASES:
        for s in _batch_spans(r, name):
            per_batch[s["args"]["batch"]] += s["t1"] - s["t0"]
    served = {s["args"]["batch"] for s in _batch_spans(r, "engine.dispatch")}
    if not served:
        return None
    return sum(per_batch[b] for b in served) / len(served) / 1e6
