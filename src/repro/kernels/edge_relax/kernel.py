"""Fused edge-relaxation kernel for HoD's level-synchronous sweeps.

TPU adaptation of the sweep hot loop (DESIGN.md §2): the *irregular* part
of a relaxation — gathering ``dist[:, src]`` — is hoisted out of the
kernel as a bulk XLA gather (TPUs handle bulk gathers well and in-kernel
random access poorly).  The HoD index then gives every level a *bucketed*
layout: each destination node of the level has a fixed-width padded list
of K in-edges.  What remains is a dense fused reduction

    out[s, m] = min( cur[s, m],  min_k  gathered[s, m, k] + w[m, k] )

which this kernel performs entirely in VMEM: one pass over the gathered
block, no f32[S,M,K] intermediate ever hits HBM (the pure-jnp version
materializes it).  Grid: (S/bs, M/bm); K is kept whole per block (bounded
by the level's max in-degree bucket).
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = float("inf")


def _relax_kernel(gathered_ref, w_ref, cur_ref, mask_ref, o_ref):
    g = gathered_ref[...]                     # [bs, bm, K]
    w = w_ref[...]                            # [bm, K]
    cur = cur_ref[...]                        # [bs, bm]
    cand = jnp.minimum(cur, jnp.min(g + w[None, :, :], axis=-1))
    valid = mask_ref[...] != 0                # [1, bm] row-validity mask
    o_ref[...] = jnp.where(valid, cand, cur)


def relax_bucketed_pallas(gathered: jnp.ndarray, w: jnp.ndarray,
                          cur: jnp.ndarray, row_valid: jnp.ndarray, *,
                          bs: int = 8, bm: int = 128,
                          interpret: bool) -> jnp.ndarray:
    """gathered: [S, M, K] (dist[:, src[m,k]]); w: [M, K]; cur: [S, M];
    row_valid: [M] bool — False rows pass ``cur`` through untouched.

    The executor scans static-shape plan levels through this one kernel
    instance; masked rows (level padding) carry +inf weights too, so the
    mask and the (min, +) absorption agree.
    """
    s, m, k = gathered.shape
    bs_ = min(bs, s)
    bm_ = min(bm, max(128, m)) if m >= 128 else m
    ss, mm = -(-s // bs_) * bs_, -(-m // bm_) * bm_
    mask = row_valid.astype(jnp.int32)[None, :]        # [1, M]
    if (ss, mm) != (s, m):
        gathered = jnp.pad(gathered, ((0, ss - s), (0, mm - m), (0, 0)),
                           constant_values=INF)
        w = jnp.pad(w, ((0, mm - m), (0, 0)), constant_values=INF)
        cur = jnp.pad(cur, ((0, ss - s), (0, mm - m)), constant_values=INF)
        mask = jnp.pad(mask, ((0, 0), (0, mm - m)), constant_values=0)

    grid = (ss // bs_, mm // bm_)
    out = pl.pallas_call(
        _relax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs_, bm_, k), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bm_, k), lambda i, j: (j, 0)),
            pl.BlockSpec((bs_, bm_), lambda i, j: (i, j)),
            pl.BlockSpec((1, bm_), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bs_, bm_), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ss, mm), cur.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(gathered, w, cur, mask)
    return out[:s, :m]
