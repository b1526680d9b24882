"""The plain reference: a binary-heap Dijkstra in pure Python.

It repeats the oracle of the repository's tests (``tests/oracle.py``)
and shares nothing with the engine under test: adjacency straight off
the benchmark's own arc arrays, exact Python floats.  On integer arc
lengths every distance is an exact integer, so the engine's float32
answers must equal it exactly.

Each guarantee a configuration states has a control (``controls_for``):
the reference with that guarantee broken, as a later change might be
tempted to break it.  Each has to fail the comparison wherever the data
lets it:

* ``labels`` at a stated precision: every tentative distance rounded to
  the precision below (``bfloat16`` for float32).  bfloat16 holds every
  integer up to 256 exactly, so this control fails only where distances
  pass 256;
* ``arcs: directed``: ``reversed``, the reference over every arc turned
  around (``dst -> src``, same length), as a search that walks the
  in-arc index in place of the out-arc one would answer;
* exact hop counts (no ``labels``, every arc of unit length):
  ``hop_capped``, paths of at most one arc less than the farthest
  sampled answer, as a search stopped one round early would give.

A guarantee value with no control is refused, so none goes unguarded.
A new control is a case in ``control`` and a rule in ``controls_for``.
"""
from __future__ import annotations

import heapq
import math
from typing import Callable, Dict, List, Optional

import numpy as np


def _round_bf16(x: float) -> float:
    """``x`` rounded to the nearest bfloat16 (ties to even)."""
    if not math.isfinite(x):
        return x
    bits = int(np.float32(x).view(np.uint32))
    bits += 0x7FFF + ((bits >> 16) & 1)
    return float(np.uint32((bits >> 16) << 16).view(np.float32))


class Reference:
    """Single-source shortest paths over one arc list."""

    def __init__(self, arcs, rounding: Optional[Callable] = None):
        order = np.argsort(arcs.src, kind="stable")
        self.n = int(arcs.n)
        self._ptr: List[int] = np.searchsorted(
            arcs.src[order], np.arange(self.n + 1)).tolist()
        self._dst: List[int] = arcs.dst[order].tolist()
        self._w: List[float] = arcs.w[order].tolist()
        self._round = rounding

    def ssd(self, s: int, target: Optional[int] = None) -> List[float]:
        """Distances from ``s``; with ``target``, exact at least for
        ``target`` (the search stops once it is settled)."""
        ptr, dst, w, rnd = self._ptr, self._dst, self._w, self._round
        dist = [math.inf] * self.n
        dist[s] = 0.0
        heap = [(0.0, s)]
        while heap:
            d, u = heapq.heappop(heap)
            if d > dist[u]:
                continue
            if u == target:
                break
            for i in range(ptr[u], ptr[u + 1]):
                v = dst[i]
                nd = d + w[i]
                if rnd is not None:
                    nd = rnd(nd)
                if nd < dist[v]:
                    dist[v] = nd
                    heapq.heappush(heap, (nd, v))
        return dist

    def p2p(self, s: int, t: int) -> float:
        return self.ssd(s, target=t)[t]


class HopCapped(Reference):
    """Shortest paths of at most ``hops`` arcs (Bellman-Ford rounds)."""

    def __init__(self, arcs, hops: int):
        super().__init__(arcs)
        self.hops = int(hops)

    def ssd(self, s: int, target: Optional[int] = None) -> List[float]:
        ptr, dst, w = self._ptr, self._dst, self._w
        dist = [math.inf] * self.n
        dist[s] = 0.0
        frontier = {s}
        for _ in range(self.hops):
            nxt = {}
            for u in frontier:
                du = dist[u]
                for i in range(ptr[u], ptr[u + 1]):
                    v = dst[i]
                    nd = du + w[i]
                    if nd < dist[v] and nd < nxt.get(v, math.inf):
                        nxt[v] = nd
            for v, nd in nxt.items():
                dist[v] = min(dist[v], nd)
            frontier = set(nxt)
            if not frontier:
                break
        return dist


#: A stated label precision -> the precision below it.
PRECISION_BELOW = {"float32": "bfloat16"}


def counts_hops(guarantees: dict, arcs) -> bool:
    """Whether answers are exact hop counts: no label precision stated
    and every arc of unit length.  Then ``hop_capped`` applies and
    ``controls_for`` needs the farthest sampled answer."""
    return "labels" not in guarantees and bool(np.all(arcs.w == 1.0))


def controls_for(guarantees: dict, arcs,
                 farthest: Optional[float]) -> Dict[str, Reference]:
    """One control per guarantee of ``guarantees`` over ``arcs``, by
    name.  ``farthest``, the largest distance the reference gives among
    the sampled answers, is read only where ``counts_hops``.  A stated
    guarantee with no control, or none to break, raises ``ValueError``."""
    controls = {}
    for key, value in guarantees.items():
        if key == "labels" and value in PRECISION_BELOW:
            name = PRECISION_BELOW[value]
        elif key == "arcs" and value == "directed":
            name = "reversed"
        elif key == "answers":
            continue
        else:
            raise ValueError(f"no control breaks the guarantee "
                             f"{key}: {value!r}")
        controls[name] = control(arcs, name)
    if counts_hops(guarantees, arcs):
        controls["hop_capped"] = control(arcs, "hop_capped",
                                         hops=int(farthest) - 1)
    if not controls:
        raise ValueError("no control: the answers are neither labels at a "
                         "stated precision, nor directed, nor hop counts")
    return controls


def control(arcs, name: str, hops: Optional[int] = None) -> Reference:
    """The control ``name`` over ``arcs``."""
    if name == "bfloat16":
        return Reference(arcs, rounding=_round_bf16)
    if name == "reversed":
        return Reference(arcs._replace(src=arcs.dst, dst=arcs.src))
    if name == "hop_capped":
        return HopCapped(arcs, hops)
    raise ValueError(f"unknown control {name!r}")
