"""The least work of one ``edge_relax`` sweep, for its roofline share.

One sweep relaxes, level by level, every real arc of the level into its
destination, for each of ``S`` batch rows.  Whatever the layout, an
implementation must at least

* read each arc's length once (4 bytes per arc),
* read each source label it relaxes from once per batch row (4 bytes
  per arc and row),
* read and write each destination's label once per batch row
  (4 + 4 bytes per destination),
* and per arc and row do one add and one min (2 operations).

Padding rows, padding arcs (``+inf`` lengths) and padding levels count
for nothing, so a kernel that stops moving padding, or fuses the gather,
cannot read above 100%.  The arcs' source indices are left out: today
an XLA gather outside the kernel reads them, and a kernel that fuses
the gather reads more than this count, never less.  ``layout_bytes``
gives what today's padded ``[S, M, K]`` layout moves per kernel call,
which bounds the least from above.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

F32 = 4
I32 = 4


class Work(NamedTuple):
    flops: float
    nbytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.nbytes + other.nbytes)

    def scaled(self, k: float) -> "Work":
        return Work(self.flops * k, self.nbytes * k)


def least_work(arcs: int, destinations: int, rows: int) -> Work:
    """Least work of relaxing ``arcs`` arcs into ``destinations`` nodes
    for ``rows`` batch rows."""
    return Work(flops=2.0 * rows * arcs,
                nbytes=float(arcs * F32 + rows * arcs * F32
                             + 2 * rows * destinations * F32))


def layout_bytes(rows: int, m: int, k: int) -> float:
    """Bytes one call of today's kernel moves at plan width ``[M, K]``:
    the gathered ``[S, M, K]`` block, ``[M, K]`` lengths, ``[S, M]``
    current labels in and out, and the ``[M]`` row mask."""
    return float(rows * m * k * F32 + m * k * F32 + 2 * rows * m * F32
                 + m * I32)


def plan_least_work(plan, rows: int) -> Work:
    """Least work of one sweep over a packed plan (arrays
    ``dst [L, M]``, ``w [L, M, K]``, ``row_valid [L, M]``,
    ``level_mask [L]``)."""
    w = np.asarray(plan.w)
    live = (np.asarray(plan.row_valid)
            & np.asarray(plan.level_mask)[:, None])
    total = Work(0.0, 0.0)
    for lvl in range(w.shape[0]):
        arcs = int((np.isfinite(w[lvl]) & live[lvl][:, None]).sum())
        dests = int(np.unique(np.asarray(plan.dst[lvl])[live[lvl]]).size)
        total = total + least_work(arcs, dests, rows)
    return total
