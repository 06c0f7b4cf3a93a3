"""Pallas TPU kernels: fused exchange-plane ops (gather+quantize,
dequantize+scatter).

The exchange hot path moves (rows → wire → rows) through three steps
that the numpy plane runs as separate passes with host staging between
them: gather rows out of the server table, int8-encode them (pull
responses), and decode+store pushed rows back into the table.  These
kernels fuse each pair so the table never leaves the device and the
intermediate fp32 block never exists in HBM:

  gather_quantize    — row-index gather from a device-resident
      (R, H) table fused with the per-row symmetric int8 encode of
      :mod:`repro.kernels.quantize`; one linear read of the touched
      rows, int8 values + fp32 scales written directly.
  dequant_scatter    — int8 decode fused with a scatter-write (push
      apply) or scatter-accumulate into the table, in place via
      ``input_output_aliases`` so the table is updated without a copy.

Both kernels share the bucketed-padding contract of
:mod:`repro.kernels.quantize`: row counts pad to the static power-of-two
bucket ladder, so a stream of delta-sized pushes compiles a bounded
number of programs.  Row *indices* pad with an out-of-range sentinel
(== R) and scatter in ``mode='drop'`` — a padded lane can never touch a
real row, which is what keeps the padded path bit-identical to the
unpadded oracle.

Quantization math is :func:`repro.kernels.ref.quantize_int8`, the same
the quantize kernel runs, so
``gather_quantize(table, rows) == quantize_int8(table[rows])`` holds
bit-exactly — the row-independent codec property the sharded transports
rely on survives the fusion.

Scatter semantics: valid ``rows`` must be unique for ``accumulate=False``
(a push's row set is — gids are unique per RPC); duplicates are allowed
for ``accumulate=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import ref
from .quantize import ROW_TILE, bucket_rows, pad_hidden, pad_rows


def _pad_idx(rows, n: int, sentinel: int) -> jax.Array:
    """Bucket-pad a row-index vector to (B, 1) int32, padding with
    ``sentinel`` (callers pass the table row count R: out-of-range, so
    ``mode='drop'`` scatters and clamped gathers can never alias a real
    row... gathers use 0 instead — see call sites)."""
    B = bucket_rows(n)
    if isinstance(rows, np.ndarray) or not isinstance(rows, jax.Array):
        idx = np.full((B, 1), sentinel, np.int32)
        idx[:n, 0] = np.asarray(rows, np.int32)
        return jnp.asarray(idx)
    return jnp.full((B, 1), sentinel, jnp.int32).at[:n, 0].set(
        rows.astype(jnp.int32))


# -- gather + quantize --------------------------------------------------------

def _gather_quantize_kernel(tbl_ref, idx_ref, v_ref, s_ref):
    """One (ROW_TILE, Hp) output block: table gather fused with the
    per-row symmetric int8 encode.

    tbl_ref: (R, Hp) fp32 (whole table, VMEM-resident);
    idx_ref: (T, 1) int32; v_ref: (T, Hp) int8; s_ref: (T, 1) fp32."""
    idx = idx_ref[...][:, 0]
    # padded lanes carry index 0 (clamped): they quantize row 0 and are
    # sliced away by the caller — never scattered anywhere.
    x = jnp.take(tbl_ref[...], idx, axis=0)
    v_ref[...], s_ref[...] = ref.quantize_int8(x)


@jax.jit
def _gather_quantize_padded_jnp(table: jax.Array, idx: jax.Array
                                ) -> tuple[jax.Array, jax.Array]:
    """Jitted jnp twin of the Pallas program: same bucket-padded shapes,
    same math — the fused device path off-TPU (ops dispatch)."""
    return ref.quantize_int8(jnp.take(table, idx[:, 0], axis=0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_quantize_padded(table: jax.Array, idx: jax.Array, *,
                            interpret: bool):
    R, H = table.shape
    B = idx.shape[0]
    return pl.pallas_call(
        _gather_quantize_kernel,
        grid=(B // ROW_TILE,),
        in_specs=[pl.BlockSpec((R, H), lambda i: (0, 0)),
                  pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((ROW_TILE, H), lambda i: (i, 0)),
                   pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((B, H), jnp.int8),
                   jax.ShapeDtypeStruct((B, 1), jnp.float32)),
        interpret=interpret,
    )(table, idx)


def gather_quantize(table: jax.Array, rows, *, interpret: bool = True,
                    via: str = "pallas") -> tuple[jax.Array, jax.Array]:
    """table (R, hidden) fp32 × rows (n,) int → (values (n, hidden) int8,
    scales (n, 1) fp32), bit-identical to ``quantize_int8(table[rows])``.

    The table stays whole (one lane-padded column block — the server's
    device tables are stored pre-aligned, so no per-call copy); rows
    bucket-pad with index 0.  ``via='jnp'`` runs the jitted jnp twin over
    the same padded shapes (the off-TPU device path)."""
    n = len(rows)
    R, h = table.shape
    if n == 0:
        return (jnp.zeros((0, h), jnp.int8), jnp.zeros((0, 1), jnp.float32))
    tbl, _, _ = pad_rows(np.asarray(table, np.float32)
                         if isinstance(table, np.ndarray) else table)
    # pad_rows bucket-pads table rows too — harmless (indices only ever
    # address real rows) and it keeps the program keyed on the table's
    # bucket, not its exact row count.
    idx = _pad_idx(rows, n, sentinel=0)
    if via == "jnp":
        vp, sp = _gather_quantize_padded_jnp(tbl, idx)
    else:
        vp, sp = _gather_quantize_padded(tbl, idx, interpret=interpret)
    return vp[:n, :h], sp[:n]


# -- dequantize + scatter -----------------------------------------------------

def _make_scatter_kernel(accumulate: bool):
    def kernel(_tbl_in_ref, idx_ref, v_ref, s_ref, out_ref):
        """One (T,)-row update tile scattered into the whole aliased
        table block.  Padded lanes carry the sentinel index R and are
        dropped by the scatter."""
        idx = idx_ref[...][:, 0]
        new = v_ref[...].astype(jnp.float32) * s_ref[...]
        tbl = out_ref[...]
        if accumulate:
            out_ref[...] = tbl.at[idx].add(new, mode="drop")
        else:
            out_ref[...] = tbl.at[idx].set(new, mode="drop")
    return kernel


@functools.partial(jax.jit, static_argnames=("accumulate",))
def _dequant_scatter_padded_jnp(table: jax.Array, idx: jax.Array,
                                values: jax.Array, scales: jax.Array, *,
                                accumulate: bool) -> jax.Array:
    """Jitted jnp twin of the Pallas scatter program — same padded
    shapes, same sentinel-drop semantics."""
    new = values.astype(jnp.float32) * scales
    i = idx[:, 0]
    if accumulate:
        return table.at[i].add(new, mode="drop")
    return table.at[i].set(new, mode="drop")


@functools.partial(jax.jit, static_argnames=("accumulate", "interpret"))
def _dequant_scatter_padded(table: jax.Array, idx: jax.Array,
                            values: jax.Array, scales: jax.Array, *,
                            accumulate: bool, interpret: bool) -> jax.Array:
    R, H = table.shape
    B = idx.shape[0]
    return pl.pallas_call(
        _make_scatter_kernel(accumulate),
        grid=(B // ROW_TILE,),
        in_specs=[pl.BlockSpec((R, H), lambda i: (0, 0)),
                  pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0)),
                  pl.BlockSpec((ROW_TILE, H), lambda i: (i, 0)),
                  pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((R, H), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, H), jnp.float32),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(table, idx, values, scales)


def dequant_scatter(table: jax.Array, rows, values, scales, *,
                    accumulate: bool = False, interpret: bool = True,
                    via: str = "pallas") -> jax.Array:
    """Decode int8 rows and scatter them into ``table`` at ``rows``.

    table (R, hidden) fp32; rows (n,) int; values (n, hidden) int8;
    scales (n, 1) fp32.  Returns the updated table as a fresh array
    (``input_output_aliases`` keeps the update in place *inside* the
    program; callers rebind their handle to the result).
    ``accumulate=False`` overwrites rows (push apply; valid rows must be
    unique), ``accumulate=True`` adds into them (partial aggregation).
    Bit-identical to ``table.at[rows].set/add(values * scales)``."""
    n = len(rows)
    R, h = table.shape
    if n == 0:
        return table if isinstance(table, jax.Array) else jnp.asarray(table)
    Hp = pad_hidden(h)
    padded_cols = Hp != h
    if isinstance(table, np.ndarray):
        tbl = np.zeros((R, Hp), np.float32)
        tbl[:, :h] = table
        tbl = jnp.asarray(tbl)
    elif padded_cols:
        tbl = jnp.zeros((R, Hp), jnp.float32).at[:, :h].set(table)
    else:
        tbl = table
    idx = _pad_idx(rows, n, sentinel=R)
    vp, _, _ = pad_rows(values if not isinstance(values, np.ndarray)
                        else np.asarray(values, np.int8))
    sp, _, _ = pad_rows(scales if not isinstance(scales, np.ndarray)
                        else np.asarray(scales, np.float32), width=1)
    if via == "jnp":
        out = _dequant_scatter_padded_jnp(tbl, idx, vp, sp,
                                          accumulate=accumulate)
    else:
        out = _dequant_scatter_padded(tbl, idx, vp, sp,
                                      accumulate=accumulate,
                                      interpret=interpret)
    return out[:, :h] if padded_cols else out
