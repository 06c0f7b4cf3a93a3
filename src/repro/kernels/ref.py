"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def gnn_aggregate(src_feats: jax.Array, ell_idx: jax.Array,
                  ell_mask: jax.Array) -> jax.Array:
    """Mean aggregation over an ELL adjacency.

    src_feats: (N_src, F); ell_idx: (N_dst, K) int32 rows into src_feats;
    ell_mask: (N_dst, K) bool.  Returns (N_dst, F) mean of valid rows
    (zeros for isolated vertices).
    """
    gathered = src_feats[ell_idx]                       # (N_dst, K, F)
    w = ell_mask.astype(src_feats.dtype)[..., None]
    s = (gathered * w).sum(axis=1)
    cnt = ell_mask.sum(axis=1).astype(src_feats.dtype)
    return s / jnp.maximum(cnt, 1.0)[:, None]


def swa_attention_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_pos: jax.Array, kv_valid: jax.Array,
                         q_pos: jax.Array, window: int) -> jax.Array:
    """Single-token sliding-window attention.

    q: (B, H, dh); k/v: (B, T, Hkv, dh); kv_pos/kv_valid: (B, T);
    q_pos: (B,).  Returns (B, H, dh)."""
    B, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, dh)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k) / np.sqrt(dh)
    mask = kv_valid & (kv_pos <= q_pos[:, None]) \
        & (kv_pos > q_pos[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(v.dtype), v)
    return out.reshape(B, H, dh)


def _f32_bits_and(x: jax.Array, mask: int) -> jax.Array:
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(mask)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _round_about(a: jax.Array, s: jax.Array, m: jax.Array) -> jax.Array:
    """``rint(fl(a / s))`` for fp32 ``a >= 0``, ``s > 0``, given an
    integer ``m`` with ``|a/s - (m + 1/2)| < 1``.

    ``b = m + 1/2`` is the only rounding boundary that close, and it is
    an even fp32, so the correctly rounded quotient is ``b`` itself iff
    ``a/s`` lies within half an ulp of ``b`` (ties go to ``b``), and
    ``rint`` then rounds ``b`` half to even.  (Below ``b = 1/2`` the ulp
    halves, but there both outcomes round to 0.)  The side is decided on
    ``v = 2a - (2m+1)·s = 2s·(a/s - b)``, which is exact whenever it is
    near the thresholds: ``s`` splits into two 12-bit halves, so both
    partial products with the odd integer ``2m+1 <= 255`` are exact.
    TPU fp32 add and multiply round correctly (only the divide does
    not), so this holds on the chip too."""
    two_b = 2.0 * m + 1.0
    s_hi = _f32_bits_and(s, -4096)             # top 12 significand bits
    v = (2.0 * a - two_b * s_hi) - two_b * (s - s_hi)
    ulp = _f32_bits_and(0.5 * two_b, 0x7F800000) * jnp.float32(2.0 ** -23)
    odd = m - 2.0 * jnp.floor(0.5 * m)
    return jnp.where(v > ulp * s, m + 1.0,
                     jnp.where(v < -ulp * s, m, m + odd))


def rint_div(x: jax.Array, s: jax.Array) -> jax.Array:
    """``np.rint(x / s)`` bit for bit, for fp32 ``x`` and ``s > 0`` with
    ``|x / s| < 128``, on every backend.

    The TPU's fp32 divide is not correctly rounded (up to 2 ulp off), so
    ``round(x / s)`` on the chip disagrees with the host in a few values
    per million.  Here the divide only locates the nearest half-integer
    boundary, and :func:`_round_about` decides the side exactly."""
    a = jnp.abs(x)
    k = _round_about(a, s, jnp.floor(a / s))
    return jnp.where(x < 0, -k, k)


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 quantization (wire codec, exchange subsystem).

    x: (n, hidden) fp32.  Returns (values int8 (n, hidden),
    scales fp32 (n, 1)) with scale = row absmax / 127 (0 for zero rows).
    The Pallas kernels run this same math on their blocks."""
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    # reciprocal-mul, not divide: what the numpy mirror computes
    scale = absmax * jnp.float32(1.0 / 127.0)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(rint_div(x, safe), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_int8(values: jax.Array, scales: jax.Array) -> jax.Array:
    """values (n, hidden) int8 × scales (n, 1) fp32 → (n, hidden) fp32."""
    return values.astype(jnp.float32) * scales.astype(jnp.float32)


# -- fused exchange-plane ops (oracles for kernels.exchange_fused) ------------

def gather_quantize(table: jax.Array, rows: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """Row gather fused with the int8 encode: the unfused two-step
    ``quantize_int8(table[rows])``, which the fused kernel must match
    bit-exactly (per-row quantization sees identical fp32 inputs)."""
    return quantize_int8(jnp.take(table.astype(jnp.float32),
                                  jnp.asarray(rows), axis=0))


def dequant_scatter(table: jax.Array, rows: jax.Array, values: jax.Array,
                    scales: jax.Array, *, accumulate: bool = False
                    ) -> jax.Array:
    """int8 decode fused with scatter into ``table`` at ``rows``:
    overwrite (push apply) or accumulate.  Returns the updated table."""
    new = dequantize_int8(values, scales)
    tbl = table.astype(jnp.float32)
    rows = jnp.asarray(rows)
    if accumulate:
        return tbl.at[rows].add(new)
    return tbl.at[rows].set(new)


def dequant_aggregate(src_values: jax.Array, src_scales: jax.Array,
                      ell_idx: jax.Array, ell_mask: jax.Array) -> jax.Array:
    """Mean aggregation straight off the wire form: dequantize the int8
    source table, then :func:`gnn_aggregate` — the two-step host path
    the fused kernel replaces."""
    return gnn_aggregate(dequantize_int8(src_values, src_scales),
                         ell_idx, ell_mask)


def topk_mask(scores: jax.Array, k: int) -> jax.Array:
    """Boolean mask of the k largest entries (ties broken towards keeping
    ≥ k entries — the threshold semantics the bisection kernel provides)."""
    if k <= 0:
        return jnp.zeros(scores.shape, bool)
    if k >= scores.shape[0]:
        return jnp.ones(scores.shape, bool)
    kth = jnp.sort(scores)[-k]
    return scores >= kth
