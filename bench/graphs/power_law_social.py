"""Graph kind ``power_law_social``: a social network's stand-in.

Repeats ``repro.core.graph.power_law_digraph`` followed by
``symmetrize`` draw for draw.
"""
import numpy as np
from yardstick.graphs import Arcs


def arcs(persons: int, m_per_node: int, seed: int) -> Arcs:
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
