"""A configuration's data: the arcs its ``graph`` block describes.

The block's ``kind`` names a generator file, ``bench/graphs/<kind>.py``,
found by ``spec.load_graph``; its ``arcs(**params)`` takes the block's
other keys and returns ``Arcs``.  A graph kind is added by adding such a
file, with no edit here.  Every generator's output is checked before
anyone uses it.  The program under test gets a ``Digraph`` built from
these arrays; the reference reads the same arrays and nothing the
program made.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from . import spec


class Arcs(NamedTuple):
    n: int
    src: np.ndarray     # int64 [m]
    dst: np.ndarray     # int64 [m]
    w: np.ndarray       # float64 [m], positive

    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n)


def generate(graph: dict, root: str = spec.ROOT) -> Arcs:
    """The arcs a configuration's ``graph`` block describes, made by the
    generator of its ``kind`` under ``<root>/bench/graphs``."""
    params = {k: v for k, v in graph.items() if k != "kind"}
    arcs = spec.load_graph(graph["kind"], root)(**params)
    _check(graph["kind"], arcs)
    return arcs


def _check(kind: str, arcs) -> None:
    """Raise ``SpecError`` unless ``arcs`` is a well-formed ``Arcs``:
    ``n`` >= 1, int64 endpoints of one length inside ``[0, n)``, and a
    finite, positive float64 length per arc."""
    def bad(why: str):
        return spec.SpecError(f"graph kind {kind!r} returned {why}")

    if not isinstance(arcs, Arcs):
        raise bad(f"a {type(arcs).__name__}, not Arcs")
    if not isinstance(arcs.n, (int, np.integer)) or arcs.n < 1:
        raise bad(f"n = {arcs.n!r}, not a whole number >= 1")
    for name, dtype in (("src", np.int64), ("dst", np.int64),
                        ("w", np.float64)):
        a = getattr(arcs, name)
        if not isinstance(a, np.ndarray) or a.dtype != dtype or a.ndim != 1:
            raise bad(f"{name} that is not a 1-d {np.dtype(dtype)} array")
        if a.shape != arcs.src.shape:
            raise bad(f"{name} of {a.shape[0]} arcs against src's "
                      f"{arcs.src.shape[0]}")
    for name in ("src", "dst"):
        a = getattr(arcs, name)
        if a.size and (a.min() < 0 or a.max() >= arcs.n):
            raise bad(f"a {name} outside [0, {arcs.n})")
    if not np.all(np.isfinite(arcs.w) & (arcs.w > 0)):
        raise bad("an arc length that is not finite and positive")
