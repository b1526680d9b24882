"""Flash-decoding attention kernel: one query token over a long KV cache.

The LM serving hot spot (decode_32k / long_500k cells).  Grid iterates KV
blocks ("arbitrary" — sequential) keeping running (max, sum, acc) softmax
statistics in the output refs; score tiles live only in VMEM.  Batch and
KV-head dims are vmapped outside (the per-(b, kh) problem is
[G, S] × [S, dh] — MXU-shaped after the GQA group dim is folded into
rows).  Length masking uses the block's global offset vs ``kv_len``.

On a real TPU this runs per split-KV shard inside the shard_map of
``attention_decode``; interpret=True validates the same body on CPU.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _decode_kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   *, bk: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]                                  # [G, dh]
    k = k_ref[...]                                  # [bk, dh]
    v = v_ref[...]                                  # [bk, dh]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [G, bk]
    pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(pos < kv_len_ref[0], s, NEG_INF)

    m_prev = m_ref[...]                             # [G, 1]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)                         # [G, bk]
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.dot(p.astype(v.dtype), v,
                 preferred_element_type=jnp.float32)  # [G, dh]
    o_ref[...] = o_ref[...] * corr + pv
    m_ref[...] = m_new


def _decode_one(q, k, v, kv_len, *, bk: int, interpret: bool):
    """q: [G, dh] (pre-scaled); k/v: [S, dh]; kv_len: [1] i32."""
    g, dh = q.shape
    s = k.shape[0]
    nk = s // bk
    out, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk),
        grid=(nk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((g, dh), lambda j: (0, 0)),
            pl.BlockSpec((bk, dh), lambda j: (j, 0)),
            pl.BlockSpec((bk, dh), lambda j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, dh), lambda j: (0, 0)),
            pl.BlockSpec((g, 1), lambda j: (0, 0)),
            pl.BlockSpec((g, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, dh), jnp.float32),
            jax.ShapeDtypeStruct((g, 1), jnp.float32),
            jax.ShapeDtypeStruct((g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(kv_len, q, k, v)
    return out / jnp.maximum(l, 1e-30)


def flash_decode_pallas(q, k_cache, v_cache, kv_len, *, block_k: int = 512,
                        interpret: bool = True):
    """q: [B, H, dh]; caches: [B, S, Kh, dh]; kv_len scalar.

    Returns [B, H, dh].  S is padded to a block multiple with masked tail.
    """
    b, h, dh = q.shape
    s, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    bk = min(block_k, s)
    pad = (-s) % bk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q = q.reshape(b, kh, g, dh) * (dh ** -0.5)
    kc = k_cache.transpose(0, 2, 1, 3)      # [B, Kh, S, dh]
    vc = v_cache.transpose(0, 2, 1, 3)
    kv_len_arr = jnp.full((1,), kv_len, jnp.int32)

    fn = functools.partial(_decode_one, bk=bk, interpret=interpret)
    out = jax.vmap(jax.vmap(fn, in_axes=(0, 0, 0, None)),
                   in_axes=(0, 0, 0, None))(q, kc, vc, kv_len_arr)
    return out.reshape(b, h, dh)
