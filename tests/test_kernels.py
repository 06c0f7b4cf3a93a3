"""Per-kernel validation: Pallas body (interpret=True) vs pure-jnp oracle,
swept over shapes/dtypes, plus hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.gnn_aggregate import gnn_aggregate as pallas_agg
from repro.kernels.swa_attention import swa_attention_decode as pallas_swa
from repro.kernels.topk_mask import topk_mask as pallas_topk


# -- gnn_aggregate ------------------------------------------------------------

@pytest.mark.parametrize("n_src,n_dst,k,f", [
    (64, 32, 5, 16), (257, 100, 5, 32), (1024, 300, 8, 96),
    (33, 500, 3, 200),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gnn_aggregate_shapes_dtypes(n_src, n_dst, k, f, dtype):
    rng = np.random.default_rng(n_src + n_dst)
    feats = jnp.asarray(rng.standard_normal((n_src, f)), dtype)
    idx = jnp.asarray(rng.integers(0, n_src, (n_dst, k)), jnp.int32)
    mask = jnp.asarray(rng.random((n_dst, k)) < 0.7)
    got = pallas_agg(feats, idx, mask, interpret=True)
    want = ref.gnn_aggregate(feats, idx, mask)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 200), st.integers(1, 150), st.integers(1, 7),
       st.integers(1, 40), st.integers(0, 2**31 - 1))
def test_gnn_aggregate_property(n_src, n_dst, k, f, seed):
    rng = np.random.default_rng(seed)
    feats = jnp.asarray(rng.standard_normal((n_src, f)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n_src, (n_dst, k)), jnp.int32)
    mask = jnp.asarray(rng.random((n_dst, k)) < 0.5)
    got = np.asarray(pallas_agg(feats, idx, mask, interpret=True))
    want = np.asarray(ref.gnn_aggregate(feats, idx, mask))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # isolated vertices → exactly zero
    iso = ~np.asarray(mask).any(axis=1)
    assert np.all(got[iso] == 0)


def test_gnn_aggregate_matches_segment_mean_path(small_shards):
    """Kernel result == the segment-mean the GNN layer actually uses."""
    shards, _ = small_shards
    sh = shards[0]
    ell_idx, ell_mask = ops.ell_from_csr(sh.indptr, sh.indices, max_deg=16)
    feats = jnp.asarray(
        np.random.default_rng(0).standard_normal(
            (len(sh.global_ids), 24)).astype(np.float32))
    got = ops.gnn_aggregate(feats, jnp.asarray(ell_idx),
                            jnp.asarray(ell_mask), use_pallas=True)
    want = ref.gnn_aggregate(feats, jnp.asarray(ell_idx),
                             jnp.asarray(ell_mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- swa_attention ------------------------------------------------------------

@pytest.mark.parametrize("B,T,Hkv,G,dh,window", [
    (2, 64, 2, 3, 16, 32), (1, 128, 1, 1, 64, 128), (3, 256, 4, 2, 32, 100),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_decode_shapes_dtypes(B, T, Hkv, G, dh, window, dtype):
    rng = np.random.default_rng(B * T)
    H = Hkv * G
    q = jnp.asarray(rng.standard_normal((B, H, dh)), dtype)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, dh)), dtype)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, dh)), dtype)
    kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    length = rng.integers(T // 2, T)
    kv_valid = kv_pos < length
    q_pos = jnp.full((B,), length - 1, jnp.int32)
    got = pallas_swa(q, k, v, kv_pos, kv_valid, q_pos, window=window,
                     interpret=True)
    want = ref.swa_attention_decode(q, k, v, kv_pos, kv_valid, q_pos, window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([16, 48]), st.integers(1, 2),
       st.integers(1, 3), st.sampled_from([8, 32]), st.integers(4, 64),
       st.integers(0, 10**6))
def test_swa_decode_property(B, T, Hkv, G, dh, window, seed):
    rng = np.random.default_rng(seed)
    H = Hkv * G
    q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, dh)), jnp.float32)
    kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    kv_valid = kv_pos < T
    q_pos = jnp.full((B,), T - 1, jnp.int32)
    got = pallas_swa(q, k, v, kv_pos, kv_valid, q_pos, window=window,
                     interpret=True)
    want = ref.swa_attention_decode(q, k, v, kv_pos, kv_valid, q_pos, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# -- topk_mask -----------------------------------------------------------------

@pytest.mark.parametrize("n,k", [(100, 10), (1024, 256), (5000, 1250),
                                 (10, 10), (64, 0)])
def test_topk_mask_counts(n, k):
    rng = np.random.default_rng(n + k)
    scores = jnp.asarray(rng.standard_normal(n), jnp.float32)
    got = pallas_topk(scores, k, interpret=True)
    want = ref.topk_mask(scores, k)
    # identical threshold semantics (distinct scores a.s. ⇒ equality)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 2000), st.data())
def test_topk_mask_property(n, data):
    k = data.draw(st.integers(0, n))
    seed = data.draw(st.integers(0, 10**6))
    rng = np.random.default_rng(seed)
    scores = jnp.asarray(rng.standard_normal(n), jnp.float32)
    got = np.asarray(pallas_topk(scores, k, interpret=True))
    # at least k selected; everything selected dominates the unselected
    assert got.sum() >= min(k, n)
    if 0 < k < n:
        sel_min = np.asarray(scores)[got].min()
        if (~got).any():
            assert sel_min >= np.asarray(scores)[~got].max()
        # no gross over-selection (ties aside, counts are exact)
        assert got.sum() <= k + np.sum(
            np.asarray(scores) == np.sort(np.asarray(scores))[-k])


def test_ops_dispatch_cpu_defaults(small_shards):
    """auto on CPU = oracle path; forced pallas = interpret mode."""
    scores = jnp.asarray(np.random.default_rng(0).standard_normal(50),
                         jnp.float32)
    a = ops.topk_mask(scores, 10, use_pallas="auto")
    b = ops.topk_mask(scores, 10, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- fused exchange kernels: gather+quantize / dequant+scatter ----------------
#
# Odd shapes on purpose: rows not a ROW_TILE multiple, hidden off the
# 128-lane boundary, empty row blocks, all-zero rows (scale 0).  Every
# path — Pallas interpret, the jitted jnp twin, the numpy mirror — must
# be bit-identical to the two-step oracle.

from repro.kernels.exchange_fused import (dequant_scatter as fused_scatter,
                                          gather_quantize as fused_gather)
from repro.kernels.gnn_aggregate import dequant_aggregate as pallas_deagg
from repro.kernels.quantize import (bucket_rows, quantize_int8,
                                    quantize_padded, row_buckets)


def _table_rows(R, h, n, seed, *, zero_row=False):
    rng = np.random.default_rng(seed)
    table = rng.standard_normal((R, h)).astype(np.float32) * 3
    if zero_row and R:
        table[R // 2] = 0.0
    rows = rng.choice(R, size=n, replace=False).astype(np.int64)
    return table, rows


@pytest.mark.parametrize("R,n,h", [
    (300, 123, 32),      # rows % ROW_TILE != 0, hidden % LANE != 0
    (257, 257, 129),     # both off-boundary, n == R
    (64, 0, 16),         # empty pull
    (512, 300, 128),     # lane-aligned hidden, odd rows
])
def test_gather_quantize_paths_bit_identical(R, n, h):
    table, rows = _table_rows(R, h, n, R + n + h, zero_row=True)
    tdev = jnp.asarray(table)
    wv, ws = ref.gather_quantize(tdev, jnp.asarray(rows))
    for got_v, got_s in (
        fused_gather(tdev, rows, interpret=True),          # Pallas body
        fused_gather(tdev, rows, via="jnp"),               # jitted twin
        ops._np_gather_quantize(table, rows),              # numpy mirror
        ops.gather_quantize(tdev, rows, use_pallas="auto"),
    ):
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(wv))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(ws))


@pytest.mark.parametrize("accumulate", [False, True])
@pytest.mark.parametrize("R,n,h", [
    (300, 123, 32), (257, 100, 129), (64, 0, 16), (512, 300, 128),
])
def test_dequant_scatter_paths_bit_identical(R, n, h, accumulate):
    table, rows = _table_rows(R, h, n, R + n + h + int(accumulate))
    values, scales = ops._np_quantize_int8(
        np.random.default_rng(7).standard_normal((n, h)).astype(np.float32))
    values[n // 2:] = 0                      # all-zero rows survive decode
    tdev = jnp.asarray(table)
    want = ref.dequant_scatter(tdev, jnp.asarray(rows), jnp.asarray(values),
                               jnp.asarray(scales), accumulate=accumulate)
    for got in (
        fused_scatter(tdev, rows, values, scales, accumulate=accumulate,
                      interpret=True),
        fused_scatter(tdev, rows, values, scales, accumulate=accumulate,
                      via="jnp"),
        ops._np_dequant_scatter(table, rows, values, scales,
                                accumulate=accumulate),
        ops.dequant_scatter(tdev, rows, values, scales,
                            accumulate=accumulate, use_pallas="auto"),
    ):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 500), st.sampled_from([1, 32, 128, 129]),
       st.integers(0, 10**6))
def test_fused_exchange_property(R, h, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(0, R + 1))
    table, rows = _table_rows(R, h, n, seed)
    tdev = jnp.asarray(table)
    gv, gs = fused_gather(tdev, rows, interpret=True)
    wv, ws = ref.gather_quantize(tdev, jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
    # scatter the gathered rows back: the stored fp32 equals the decode
    out = fused_scatter(tdev, rows, np.asarray(gv), np.asarray(gs),
                        interpret=True)
    want = ref.dequant_scatter(tdev, jnp.asarray(rows), wv, ws)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("n_src,n_dst,k,h", [
    (300, 100, 5, 32), (257, 257, 3, 129), (64, 30, 4, 128),
])
def test_dequant_aggregate_matches_two_step(n_src, n_dst, k, h):
    """Fused dequant→ELL-mean == host dequant then gnn_aggregate, bit
    for bit, on all dispatch paths."""
    rng = np.random.default_rng(n_src + h)
    values, scales = ops._np_quantize_int8(
        rng.standard_normal((n_src, h)).astype(np.float32))
    idx = rng.integers(0, n_src, (n_dst, k)).astype(np.int32)
    mask = rng.random((n_dst, k)) < 0.7
    feats = ops.dequantize_int8(jnp.asarray(values), jnp.asarray(scales),
                                use_pallas="auto")
    want = ops.gnn_aggregate(feats, jnp.asarray(idx), jnp.asarray(mask),
                             use_pallas="auto")
    for got in (
        pallas_deagg(jnp.asarray(values), jnp.asarray(scales),
                     jnp.asarray(idx), jnp.asarray(mask), interpret=True),
        ops.dequant_aggregate(values, scales, idx, mask, use_pallas="auto"),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- int8 rounding: exact on every backend -----------------------------------

def _ulp_walk(x: np.ndarray, steps: int) -> np.ndarray:
    """x and its ``steps`` fp32 neighbours on either side."""
    out = [x]
    up, down = x.copy(), x.copy()
    for _ in range(steps):
        up = np.nextafter(up, np.float32(np.inf))
        down = np.nextafter(down, np.float32(-np.inf))
        out += [up, down]
    return np.concatenate(out)


def test_rint_div_matches_numpy_at_rounding_boundaries():
    """Quotients at and a few ulps around every half-integer boundary
    (incl. exact ties and double-rounding cases), both signs: the exact
    side decision reproduces numpy's correctly rounded divide + rint."""
    rng = np.random.default_rng(0)
    s = np.concatenate([rng.uniform(1e-4, 10.0, 3000),
                        2.0 ** rng.integers(-8, 4, 200),      # exact ties
                        np.full(64, 0.75)]).astype(np.float32)
    b = (rng.integers(0, 128, s.size) + 0.5).astype(np.float32)
    x = _ulp_walk((b * s).astype(np.float32), 4)
    x = np.concatenate([x, -x])
    ss = np.tile(s, 18)
    want = np.rint(x / ss)
    got = np.asarray(ref.rint_div(jnp.asarray(x), jnp.asarray(ss)))
    np.testing.assert_array_equal(got, want)


def test_round_about_tolerates_an_inexact_divide():
    """A divide up to a few ulps off (the TPU's) can put ``floor(a/s)``
    on either side of an integer quotient; both candidates must round
    the same as numpy."""
    rng = np.random.default_rng(1)
    s = rng.uniform(1e-4, 10.0, 2000).astype(np.float32)
    k = rng.integers(1, 128, s.size).astype(np.float32)
    a = _ulp_walk((k * s).astype(np.float32), 3)
    ss, kk = np.tile(s, 7), np.tile(k, 7)
    want = np.rint(a / ss)
    for m in (kk - 1.0, kk):
        got = ref._round_about(jnp.asarray(a), jnp.asarray(ss),
                               jnp.asarray(m))
        np.testing.assert_array_equal(np.asarray(got), want)


# -- bucketed padding: retrace guard + boundary bit-identity ------------------

def test_bucketed_quantize_retrace_guard():
    """50 pushes with 50 distinct row counts compile at most one program
    per bucket (the quantize program is keyed on the bucket shape, never
    the row count)."""
    h = 32
    before = quantize_padded._cache_size()
    rng = np.random.default_rng(0)
    counts = rng.choice(np.arange(1, 4000), size=50, replace=False)
    for n in counts:
        x = jnp.asarray(rng.standard_normal((int(n), h)), jnp.float32)
        quantize_int8(x, interpret=True)
    grown = quantize_padded._cache_size() - before
    assert grown <= len(row_buckets()), \
        f"{grown} compiles for 50 row counts (buckets: {row_buckets()})"
    assert grown <= len({bucket_rows(int(n)) for n in counts})


@pytest.mark.parametrize("bucket", [256, 512])
def test_bucket_boundary_bit_identity(bucket):
    """n = bucket-1 / bucket / bucket+1 all round-trip bit-identically
    to the numpy oracle — the pad rows never leak into real rows."""
    h = 48
    rng = np.random.default_rng(bucket)
    for n in (bucket - 1, bucket, bucket + 1):
        x = (rng.standard_normal((n, h)) * 2).astype(np.float32)
        nv, ns = ops._np_quantize_int8(x)
        for pv, ps in (quantize_int8(jnp.asarray(x), interpret=True),
                       quantize_int8(x, interpret=True)):
            assert pv.shape == (n, h) and ps.shape == (n, 1)
            np.testing.assert_array_equal(np.asarray(pv), nv)
            np.testing.assert_array_equal(np.asarray(ps), ns)


# -- ell_from_csr: vectorized construction vs the reference loop --------------

def _ell_from_csr_loop(indptr, indices, max_deg):
    n = len(indptr) - 1
    idx = np.zeros((n, max_deg), np.int32)
    mask = np.zeros((n, max_deg), bool)
    for v in range(n):
        nbrs = indices[indptr[v]:indptr[v + 1]][:max_deg]
        idx[v, :len(nbrs)] = nbrs
        mask[v, :len(nbrs)] = True
    return idx, mask


@pytest.mark.parametrize("n,avg_deg,max_deg", [
    (1, 0, 4), (50, 3, 5), (200, 12, 8), (97, 1, 1),
])
def test_ell_from_csr_matches_loop(n, avg_deg, max_deg):
    rng = np.random.default_rng(n + max_deg)
    deg = rng.poisson(avg_deg, n)
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
    indices = rng.integers(0, n, indptr[-1]).astype(np.int32)
    got = ops.ell_from_csr(indptr, indices, max_deg)
    want = _ell_from_csr_loop(indptr, indices, max_deg)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_ell_from_csr_empty_graph():
    idx, mask = ops.ell_from_csr(np.zeros(1, np.int64),
                                 np.zeros(0, np.int32), 4)
    assert idx.shape == (0, 4) and mask.shape == (0, 4)
