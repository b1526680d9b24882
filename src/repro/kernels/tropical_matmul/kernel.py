"""Blocked min-plus matmul as a Pallas TPU kernel.

TPU adaptation notes (vs. the paper's in-memory Dijkstra core search):
the MXU only does (+, ×) contractions, so the (min, +) semiring runs on the
VPU.  We tile exactly like a matmul — grid (M/bm, N/bn, K/bk), the K axis
innermost and "arbitrary" so each (i, j) output tile accumulates a running
elementwise min across K blocks held in VMEM.  Inside a block the K
reduction is sub-chunked (KI=8) so the [bm, KI, bn] broadcast intermediate
stays ~0.5 MB, far under VMEM.  The sub-chunks are unrolled with static
slices: Mosaic cannot lower a dynamic lane-axis slice whose start it cannot
prove 128-aligned, so a ``fori_loop`` over KI-wide chunks does not compile
for the chip.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = float("inf")  # python literal: kernels must not capture traced consts
KI = 8  # inner K sub-chunk: [bm, KI, bn] is the largest VMEM intermediate


def _minplus_kernel(a_ref, b_ref, o_ref, *, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, INF)

    a = a_ref[...]          # [bm, bk]
    b = b_ref[...]          # [bk, bn]
    acc = o_ref[...]
    for lo in range(0, bk, KI):
        a_sub = a[:, lo:lo + KI]
        b_sub = b[lo:lo + KI, :]
        acc = jnp.minimum(acc, jnp.min(a_sub[:, :, None] + b_sub[None, :, :],
                                       axis=1))
    o_ref[...] = acc


def minplus_pallas(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
                   bn: int = 128, bk: int = 128,
                   interpret: bool) -> jnp.ndarray:
    """min-plus matmul; operands padded with +inf to block multiples.

    +inf padding is absorbing for (min, +): padded lanes never win.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm_ = min(bm, max(8, -(-m // 8) * 8))
    bn_ = min(bn, max(128, -(-n // 128) * 128))
    bk_ = min(bk, max(KI, -(-k // KI) * KI))

    mm, nn, kk = (-(-m // bm_) * bm_, -(-n // bn_) * bn_, -(-k // bk_) * bk_)
    a = jnp.pad(a, ((0, mm - m), (0, kk - k)), constant_values=INF)
    b = jnp.pad(b, ((0, kk - k), (0, nn - n)), constant_values=INF)

    grid = (mm // bm_, nn // bn_, kk // bk_)
    out = pl.pallas_call(
        functools.partial(_minplus_kernel, bk=bk_),
        name="tropical_minplus",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kq: (i, kq)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kq: (kq, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kq: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]
