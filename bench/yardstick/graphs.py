"""The benchmark's own graph generators: a configuration's data.

Each returns ``Arcs`` (node count and parallel ``src``/``dst``/``w``
arrays), made from the configuration's ``graph`` block alone.  The
program under test gets a ``Digraph`` built from these arrays; the
reference reads the same arrays and nothing the program made.

The two generators repeat ``repro.core.graph.grid_road_graph`` and
``power_law_digraph`` + ``symmetrize`` draw for draw, so the served
graphs are the ones the repository's own tools make from the same seed.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Arcs(NamedTuple):
    n: int
    src: np.ndarray     # int64 [m]
    dst: np.ndarray     # int64 [m]
    w: np.ndarray       # float64 [m], positive

    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n)


def grid_road(side: int, seed: int, weight_min: int = 1,
              weight_max: int = 5) -> Arcs:
    """4-connected ``side x side`` grid, both directions of every street,
    each arc an integer travel time in ``[weight_min, weight_max]``."""
    rng = np.random.default_rng(seed)
    idx = np.arange(side * side, dtype=np.int64).reshape(side, side)
    src_l, dst_l = [], []
    for s, d in ((idx[:, :-1].ravel(), idx[:, 1:].ravel()),
                 (idx[:-1, :].ravel(), idx[1:, :].ravel())):
        src_l += [s, d]
        dst_l += [d, s]
    src = np.concatenate(src_l)
    dst = np.concatenate(dst_l)
    w = rng.integers(weight_min, weight_max + 1,
                     size=src.shape[0]).astype(np.float64)
    return Arcs(side * side, src, dst, w)


def power_law_social(persons: int, m_per_node: int, seed: int) -> Arcs:
    """Preferential attachment, ``m_per_node`` links per joining person,
    each link in both directions with length 1 (knows is symmetric)."""
    rng = np.random.default_rng(seed)
    src_l, dst_l = [], []
    targets = np.arange(min(m_per_node, persons), dtype=np.int64)
    repeated = list(targets)
    for v in range(len(targets), persons):
        picks = rng.choice(len(repeated),
                           size=min(m_per_node, len(repeated)),
                           replace=False)
        for p in picks:
            u = repeated[p]
            if rng.random() < 0.5:
                src_l.append(v)
                dst_l.append(u)
            else:
                src_l.append(u)
                dst_l.append(v)
            repeated.append(u)
        repeated.extend([v] * m_per_node)
    src = np.asarray(src_l, dtype=np.int64)
    dst = np.asarray(dst_l, dtype=np.int64)
    both_src = np.concatenate([src, dst])
    both_dst = np.concatenate([dst, src])
    return Arcs(persons, both_src, both_dst, np.ones(both_src.shape[0]))


KINDS = {"grid_road": grid_road, "power_law_social": power_law_social}


def generate(graph: dict) -> Arcs:
    """The arcs a configuration's ``graph`` block describes."""
    params = {k: v for k, v in graph.items() if k != "kind"}
    return KINDS[graph["kind"]](**params)
