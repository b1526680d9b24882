"""Bodies shared by the per-layer readers (``bench/metrics/<name>.py``)
of one quantity split by the cell's query mode: each ``<name>.<mode>.py``
reads its cells with the body named here."""
from __future__ import annotations

from typing import Optional

from .tracing import program_time


def device_idle_share(r) -> float:
    """Share of the traced window in which no device operation ran, in %."""
    t = r.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def engine_device_ms(r) -> Optional[float]:
    """Device time per run of ``QueryEngine``'s jitted program for the
    cell's mode (``_<mode>_impl``), in ms; ``None`` where none ran."""
    runs = program_time(r.trace, f"_{r.cell.traffic['mode']}_impl")
    return runs[1] / runs[0] * 1e3 if runs else None
