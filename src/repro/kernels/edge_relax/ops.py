"""jit'd wrapper: gather (XLA) + fused relax (Pallas)."""
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import relax_bucketed_pallas
from .ref import relax_bucketed_ref

#: Incremented once per (re)trace of :func:`relax_bucketed` — the Python
#: body of a jitted function only runs on a compile-cache miss.  The
#: serving tests use the delta as a compile-count regression guard: under
#: the SweepPlan executor one SSD query traces the relax exactly once per
#: sweep direction, independent of the graph's level count.
TRACE_COUNT = 0


@functools.partial(jax.jit,
                   static_argnames=("use_pallas", "interpret"))
def relax_bucketed(dist: jnp.ndarray, src_idx: jnp.ndarray,
                   w: jnp.ndarray, cur: jnp.ndarray,
                   row_valid: Optional[jnp.ndarray] = None,
                   use_pallas: bool = True, *,
                   interpret: bool) -> jnp.ndarray:
    """One plan level's relaxation over a bucketed in-edge layout.

    dist: [S, N] finalized distances; src_idx: [M, K] source node of each
    (dst-bucketed, padded) in-edge; w: [M, K] lengths (+inf padding);
    cur: [S, M] current values of the level's nodes; row_valid: [M] bool
    (None = all valid) — padding rows of a scanned SweepPlan level pass
    ``cur`` through untouched.  Returns updated cur.
    """
    global TRACE_COUNT
    TRACE_COUNT += 1
    gathered = dist[:, src_idx.reshape(-1)].reshape(
        dist.shape[0], *src_idx.shape)
    if row_valid is None:
        row_valid = jnp.ones(src_idx.shape[0], jnp.bool_)
    if use_pallas:
        return relax_bucketed_pallas(gathered, w, cur, row_valid,
                                     interpret=interpret)
    return relax_bucketed_ref(gathered, w, cur, row_valid)


__all__ = ["relax_bucketed", "relax_bucketed_ref", "TRACE_COUNT"]
