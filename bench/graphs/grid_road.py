"""Graph kind ``grid_road``: a road network's stand-in.

Repeats ``repro.core.graph.grid_road_graph`` draw for draw.
"""
import numpy as np
from yardstick.graphs import Arcs


def arcs(side: int, seed: int, weight_min: int = 1,
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
