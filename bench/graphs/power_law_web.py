"""Graph kind ``power_law_web``: a directed web graph's stand-in.

Repeats ``repro.core.graph.power_law_digraph(..., weighted=True)`` draw
for draw, and does not symmetrize.
"""
import numpy as np
from yardstick.graphs import Arcs


def arcs(nodes: int, m_per_node: int, seed: int) -> Arcs:
    """Preferential attachment, ``m_per_node`` links per joining page,
    each link one arc whose direction is a fair coin; then one integer
    length in ``[1, 10]`` per arc."""
    rng = np.random.default_rng(seed)
    src_l, dst_l = [], []
    targets = np.arange(min(m_per_node, nodes), dtype=np.int64)
    repeated = list(targets)
    for v in range(len(targets), nodes):
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
    w = rng.integers(1, 11, size=src.shape[0]).astype(np.float64)
    return Arcs(nodes, src, np.asarray(dst_l, dtype=np.int64), w)
