"""Fused embedding-bag reduction (DLRM hot path).

Same hoisting principle as edge_relax: the ragged gather runs as a bulk
XLA gather; the kernel fuses the masked bag-sum (+ optional per-sample
weights) so the [B, K, D] gathered block is consumed in VMEM instead of
being re-materialized for the reduce.  Grid: (B/bb, D/bd) with K whole.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(g_ref, m_ref, o_ref):
    g = g_ref[...]                      # [bb, K, bd]
    m = m_ref[...]                      # [bb, K]
    o_ref[...] = jnp.sum(g * m[..., None].astype(g.dtype), axis=1)


def bag_sum_pallas(gathered: jnp.ndarray, mask: jnp.ndarray, *,
                   bb: int = 16, bd: int = 128,
                   interpret: bool = True) -> jnp.ndarray:
    """gathered: [B, K, D] rows per bag (padded); mask: [B, K] validity."""
    b, k, d = gathered.shape
    bb_ = min(bb, b)
    bd_ = min(bd, d) if d >= 128 else d
    bbp, ddp = -(-b // bb_) * bb_, -(-d // bd_) * bd_
    if (bbp, ddp) != (b, d):
        gathered = jnp.pad(gathered, ((0, bbp - b), (0, 0), (0, ddp - d)))
        mask = jnp.pad(mask, ((0, bbp - b), (0, 0)))

    grid = (bbp // bb_, ddp // bd_)
    out = pl.pallas_call(
        _bag_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb_, k, bd_), lambda i, j: (i, 0, j)),
            pl.BlockSpec((bb_, k), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb_, bd_), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bbp, ddp), gathered.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(gathered, mask)
    return out[:b, :d]
