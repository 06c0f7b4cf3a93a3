"""Pallas TPU kernels: per-row symmetric int8 quantize / dequantize.

The exchange subsystem's int8 wire codec (repro.exchange.codec) makes
encode/decode a per-round compute hot path: every push and pull of the
embedding tables quantizes (n, hidden) fp32 rows to int8 plus one fp32
scale per row.  At TPU scale (Papers: ~40M boundary rows × 128 features
per round) that is a pure bandwidth-bound streaming kernel, so we tile
over rows, keep the full (padded) feature width per block, and fuse
absmax → scale → round/clip in VMEM — one linear read of the table, one
linear write of values + scales, no HBM round-trips for the reduction.

Scheme (row-independent by construction — this is what keeps sharded
transports bit-identical to single-shard ones):

  scale_i = max_j |x_ij| / 127          (0 for all-zero rows)
  q_ij    = clip(round(x_ij / scale_i), -127, 127)   int8
  x'_ij   = q_ij * scale_i

Round-to-nearest (ties-to-even, as ``np.rint``) keeps the kernel
deterministic, so encode(decode(encode(x))) is stable.  The kernel runs
the oracle's own math (:func:`repro.kernels.ref.quantize_int8`), whose
rounding does not trust the TPU's divide (not correctly rounded), so
Pallas, jnp and numpy agree bit for bit on the chip as well.

Bucketed padding
----------------
Delta-filtered pushes hand this module a different row count every
round.  Rows therefore pad to a small static set of power-of-two
buckets (``ROW_BUCKETS``, multiples of cap above it), not to the exact
ROW_TILE multiple: the quantize/dequantize programs are keyed on the
*bucket* shape, so an arbitrary stream of row counts compiles at most
``len(row_buckets(...))`` distinct programs per hidden width — the
bound ``tests/test_kernels.py`` pins with a compile counter.

Where the pad runs depends on where the data lives:

  * numpy input — the rows are host-resident (a socket payload, a
    trainer batch), so the bucket pad is one host copy into the pinned
    staging buffer that the host→device transfer needs anyway.
  * jax.Array input — the rows never leave the device: a jitted
    ``jnp`` scatter (:func:`pad_rows`) pads in-place-shape, and the
    bucket-keyed program runs on the result.  The pad itself is a
    trivial per-shape copy program; the fused quantize program stays
    bucket-keyed.

Zero padding cannot raise a row's absmax, so padded results slice back
exactly — all-zero pad rows quantize to (0, scale 0) and never leak.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import ref

ROW_TILE = 256
LANE = 128
#: largest power-of-two row bucket; row counts beyond it round up to a
#: multiple of the cap (one extra program per cap multiple, amortized).
BUCKET_CAP = 16384


def row_buckets(cap: int = BUCKET_CAP) -> tuple[int, ...]:
    """The static bucket ladder: ROW_TILE, then doublings up to ``cap``."""
    out, b = [], ROW_TILE
    while b <= cap:
        out.append(b)
        b *= 2
    return tuple(out)


def bucket_rows(n: int) -> int:
    """Smallest bucket holding ``n`` rows (cap multiples past the cap)."""
    if n <= 0:
        return ROW_TILE
    if n > BUCKET_CAP:
        return n + (-n % BUCKET_CAP)
    b = ROW_TILE
    while b < n:
        b *= 2
    return b


def pad_hidden(h: int) -> int:
    """Feature width padded to the 128-lane boundary."""
    return h + (-h % LANE)


@functools.partial(jax.jit, static_argnames=("bucket", "hp"))
def _pad_rows_dev(x: jax.Array, *, bucket: int, hp: int) -> jax.Array:
    """Device-side bucket pad: zeros(bucket, hp) with x scattered in.
    A per-(n, h) copy program — cheap glue; the fused kernels it feeds
    stay keyed on (bucket, hp)."""
    n, h = x.shape
    return jnp.zeros((bucket, hp), x.dtype).at[:n, :h].set(x)


def pad_rows(x, *, dtype=None, width: int | None = None
             ) -> tuple[jax.Array, int, int]:
    """Bucket-pad an (n, h) block → (padded (B, Hp) device array, n, h).

    ``width`` overrides the padded feature width (default: ``h``
    rounded to the 128-lane boundary; scale columns pass ``width=1``).

    numpy input pads on the host (the rows must cross host→device
    anyway — one staging copy, zero extra round-trips); device input
    pads in-jit and never touches the host."""
    n, h = x.shape
    B = bucket_rows(n)
    Hp = pad_hidden(h) if width is None else width
    if isinstance(x, np.ndarray):
        dt = np.dtype(dtype or x.dtype)
        xp = np.zeros((B, Hp), dt)
        xp[:n, :h] = x
        return jnp.asarray(xp), n, h
    xd = x if dtype is None else x.astype(dtype)
    if xd.shape == (B, Hp):
        return xd, n, h
    return _pad_rows_dev(xd, bucket=B, hp=Hp), n, h


def _quantize_kernel(x_ref, v_ref, s_ref):
    """One (ROW_TILE, H_padded) block: fused absmax + scale + round/clip.

    x_ref: (R, H) fp32; v_ref: (R, H) int8; s_ref: (R, 1) fp32."""
    v_ref[...], s_ref[...] = ref.quantize_int8(x_ref[...])


def _dequantize_kernel(v_ref, s_ref, out_ref):
    """out = values × per-row scale (zero-scale rows stay exactly zero)."""
    out_ref[...] = v_ref[...].astype(jnp.float32) * s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_padded(xp: jax.Array, *, interpret: bool = True
                    ) -> tuple[jax.Array, jax.Array]:
    """Pallas call over a bucket-aligned (B, Hp) block → (values int8
    (B, Hp), scales fp32 (B, 1)), both still bucket-shaped.  This is the
    program the compile-count bound covers: one compile per (bucket,
    Hp), never per row count."""
    R, H = xp.shape
    return pl.pallas_call(
        _quantize_kernel,
        grid=(R // ROW_TILE,),
        in_specs=[pl.BlockSpec((ROW_TILE, H), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((ROW_TILE, H), lambda i: (i, 0)),
                   pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((R, H), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)),
        interpret=interpret,
    )(xp)


def quantize_int8(x: jax.Array, *, interpret: bool = True
                  ) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 quantization.

    x: (n, hidden) fp32.  Returns (values (n, hidden) int8,
    scales (n, 1) fp32).  Input bucket-pads per the module contract
    (host copy for numpy, in-jit scatter for device arrays); the Pallas
    program compiles once per bucket, not once per row count."""
    n, h = x.shape
    if n == 0:  # zero-row grid is illegal in pallas_call; nothing to do
        return (jnp.zeros((0, h), jnp.int8), jnp.zeros((0, 1), jnp.float32))
    if isinstance(x, np.ndarray):
        xp, _, _ = pad_rows(x, dtype=np.float32)
        values, scales = quantize_padded(xp, interpret=interpret)
        return (jnp.asarray(np.asarray(values)[:n, :h]),
                jnp.asarray(np.asarray(scales)[:n]))
    xp, _, _ = pad_rows(x.astype(jnp.float32))
    values, scales = quantize_padded(xp, interpret=interpret)
    return values[:n, :h], scales[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_padded(vp: jax.Array, sp: jax.Array, *,
                      interpret: bool = True) -> jax.Array:
    """Pallas call over bucket-aligned int8 values + scales → fp32,
    bucket-shaped.  Same compile-count contract as
    :func:`quantize_padded`."""
    R, H = vp.shape
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(R // ROW_TILE,),
        in_specs=[pl.BlockSpec((ROW_TILE, H), lambda i: (i, 0)),
                  pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROW_TILE, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, H), jnp.float32),
        interpret=interpret,
    )(vp, sp)


def dequantize_int8(values: jax.Array, scales: jax.Array, *,
                    interpret: bool = True) -> jax.Array:
    """Inverse of :func:`quantize_int8`: (n, hidden) int8 × (n, 1) fp32
    scales → (n, hidden) fp32.  Same bucketed-padding contract."""
    n, h = values.shape
    if n == 0:
        return jnp.zeros((0, h), jnp.float32)
    if isinstance(values, np.ndarray):
        vp, _, _ = pad_rows(values, dtype=np.int8)
        sp, _, _ = pad_rows(np.asarray(scales, np.float32), width=1)
        out = dequantize_padded(vp, sp, interpret=interpret)
        return jnp.asarray(np.asarray(out)[:n, :h])
    vp, _, _ = pad_rows(values)
    sp, _, _ = pad_rows(scales.astype(jnp.float32), width=1)
    out = dequantize_padded(vp, sp, interpret=interpret)
    return out[:n, :h]


# -- kernel-compile telemetry -------------------------------------------------
# jax.jit re-traces per distinct bucket shape; the bucketed-padding
# contract (tests/test_kernels.py) bounds these at O(log rows) per
# kernel.  Exposed as fn-backed gauges so an OP_METRICS scrape shows
# live compile-cache sizes without importing jax internals anywhere
# else.
from repro.obsv.metrics import REGISTRY as _REGISTRY  # noqa: E402

_REGISTRY.gauge("kernels.quantize_padded.compiles",
                fn=quantize_padded._cache_size)
_REGISTRY.gauge("kernels.dequantize_padded.compiles",
                fn=dequantize_padded._cache_size)
