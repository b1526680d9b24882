"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------- tropical
@pytest.mark.parametrize("m,k,n", [
    (1, 1, 1), (4, 7, 9), (8, 128, 128), (64, 130, 257), (128, 128, 384),
    (33, 65, 5),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_tropical_matmul(m, k, n, dtype):
    from repro.kernels.tropical_matmul.ops import minplus, minplus_ref
    a = jnp.asarray(RNG.uniform(0, 10, (m, k)), dtype)
    b = jnp.asarray(RNG.uniform(0, 10, (k, n)), dtype)
    # inject +inf (unreachable) entries — absorbing element
    a = a.at[0, 0].set(jnp.inf)
    out = minplus(a, b, interpret=True)
    ref = minplus_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


# --------------------------------------------------------------- edge_relax
@pytest.mark.parametrize("s,n,m,k", [
    (1, 10, 3, 1), (4, 100, 37, 5), (8, 300, 128, 9), (3, 64, 200, 2),
])
def test_edge_relax(s, n, m, k):
    from repro.kernels.edge_relax.ops import relax_bucketed
    dist = jnp.asarray(RNG.uniform(0, 10, (s, n)), jnp.float32)
    src = jnp.asarray(RNG.integers(0, n, (m, k)), jnp.int32)
    w = jnp.asarray(RNG.uniform(0, 3, (m, k)), jnp.float32)
    if k > 1:  # padding lanes
        w = w.at[:, -1].set(jnp.inf)
    cur = jnp.asarray(RNG.uniform(0, 20, (s, m)), jnp.float32)
    a = relax_bucketed(dist, src, w, cur, use_pallas=True, interpret=True)
    b = relax_bucketed(dist, src, w, cur, use_pallas=False,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.parametrize("s,n,m,k", [(4, 100, 37, 5), (3, 64, 200, 2)])
def test_edge_relax_row_validity_mask(s, n, m, k):
    """Masked (padding) rows of a scanned plan level pass ``cur`` through
    untouched, in both the Pallas kernel and the jnp oracle."""
    from repro.kernels.edge_relax.ops import relax_bucketed
    dist = jnp.asarray(RNG.uniform(0, 10, (s, n)), jnp.float32)
    src = jnp.asarray(RNG.integers(0, n, (m, k)), jnp.int32)
    w = jnp.asarray(RNG.uniform(0, 3, (m, k)), jnp.float32)
    cur = jnp.asarray(RNG.uniform(0, 20, (s, m)), jnp.float32)
    # row 0 is masked AND would win (zero weights): the mask must suppress it
    w = w.at[0].set(0.0)
    valid = jnp.asarray(RNG.random(m) < 0.6).at[0].set(False)
    a = relax_bucketed(dist, src, w, cur, row_valid=valid, use_pallas=True,
                       interpret=True)
    b = relax_bucketed(dist, src, w, cur, row_valid=valid, use_pallas=False,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    inval = ~np.asarray(valid)
    np.testing.assert_array_equal(np.asarray(a)[:, inval],
                                  np.asarray(cur)[:, inval])


# ------------------------------------------------------------ embedding_bag
@pytest.mark.parametrize("v,d,b,k", [
    (10, 8, 3, 2), (50, 24, 9, 6), (100, 128, 32, 4), (7, 64, 17, 1),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag(v, d, b, k, dtype):
    from repro.kernels.embedding_bag.ops import bag_sum
    tab = jnp.asarray(RNG.normal(size=(v, d)), dtype)
    ids = jnp.asarray(RNG.integers(0, v, (b, k)), jnp.int32)
    mask = jnp.asarray(RNG.random((b, k)) < 0.7)
    a = bag_sum(tab, ids, mask, use_pallas=True)
    b_ = bag_sum(tab, ids, mask, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b_, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6)


# ------------------------------------------------------------- flash_decode
@pytest.mark.parametrize("b,h,kh,dh,smax,kv_len,blk", [
    (1, 4, 4, 16, 64, 1, 32),
    (2, 8, 2, 16, 96, 17, 32),
    (2, 8, 8, 32, 128, 128, 64),
    (1, 16, 4, 64, 256, 200, 128),
])
def test_flash_decode(b, h, kh, dh, smax, kv_len, blk):
    from repro.kernels.flash_decode.ops import flash_decode, flash_decode_ref
    q = jnp.asarray(RNG.normal(size=(b, h, dh)), jnp.float32)
    kc = jnp.asarray(RNG.normal(size=(b, smax, kh, dh)), jnp.float32)
    vc = jnp.asarray(RNG.normal(size=(b, smax, kh, dh)), jnp.float32)
    a = flash_decode(q, kc, vc, kv_len, block_k=blk, use_pallas=True)
    r = flash_decode_ref(q, kc, vc, kv_len)
    np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=2e-5)


def test_flash_decode_bf16_cache():
    from repro.kernels.flash_decode.ops import flash_decode, flash_decode_ref
    q = jnp.asarray(RNG.normal(size=(2, 8, 32)), jnp.float32)
    kc = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.bfloat16)
    vc = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.bfloat16)
    a = flash_decode(q, kc, vc, 100, block_k=64)
    r = flash_decode_ref(q, kc, vc, 100)
    np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                               rtol=2e-2, atol=2e-2)


def test_minplus_matches_core_search():
    """The Pallas tropical matmul plugs into QueryEngine (use_pallas=True)
    and must give identical SSD results."""
    from repro.core import (BuildConfig, QueryEngine, build_hod,
                            gnm_random_digraph, pack_index)
    g = gnm_random_digraph(150, 600, seed=9)
    res = build_hod(g, BuildConfig(max_core_nodes=32, max_core_edges=1024))
    ix = pack_index(g, res, chunk=64)
    srcs = np.array([0, 75], dtype=np.int32)
    d_ref = QueryEngine(ix, use_pallas=False).ssd(srcs)
    d_pal = QueryEngine(ix, use_pallas=True).ssd(srcs)
    np.testing.assert_allclose(d_ref, d_pal, rtol=1e-6)
