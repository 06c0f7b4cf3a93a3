"""Jit'd dispatchers over the Pallas kernels and their XLA twins.

``use_pallas='auto'`` picks the Pallas path on TPU backends only for the
kernels the TPU compiler accepts (``_COMPILES_ON_TPU``), and the jnp /
numpy paths everywhere else.  ``use_pallas=True`` always runs the Pallas
body: in interpret mode off the chip (how the tests validate every
kernel on CPU), compiled on a TPU, where a kernel outside
``_COMPILES_ON_TPU`` raises the compiler's error.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import exchange_fused as _fused
from . import gnn_aggregate as _agg
from . import quantize as _quant
from . import ref
from . import swa_attention as _swa
from . import topk_mask as _topk

#: Kernels whose Pallas body compiles for TPU v5e (jax 0.9);
#: tests/test_tpu_compile.py compiles each for a described v5e chip.  The
#: Pallas TPU lowering refuses the rest, so "auto" never hands them to it:
#:   gather_quantize, gnn_aggregate, dequant_aggregate (in-kernel
#:     ``jnp.take``): "ValueError: Shape mismatch in input, indices and
#:     output"
#:   dequant_scatter (in-kernel ``.at[].set/add``): "NotImplementedError:
#:     Unimplemented primitive in Pallas TPU lowering for KernelType.TC:
#:     scatter"
#:   topk_mask: "ValueError: Cannot store scalars to VMEM"
#:   swa_attention_decode: "ValueError: The Pallas TPU lowering currently
#:     requires that the last two dimensions of your block shape are
#:     divisible by 8 and 128 respectively, or be equal to the respective
#:     dimensions of the overall array"
_COMPILES_ON_TPU = frozenset({"quantize_int8", "dequantize_int8"})


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(use_pallas, kernel: str) -> tuple[bool, bool]:
    """→ (use_pallas, interpret)."""
    on_tpu = _on_tpu()
    if use_pallas == "auto":
        return on_tpu and kernel in _COMPILES_ON_TPU, not on_tpu
    return bool(use_pallas), not on_tpu


def gnn_aggregate(src_feats, ell_idx, ell_mask, *, use_pallas="auto"):
    use, interp = _resolve(use_pallas, "gnn_aggregate")
    if use:
        return _agg.gnn_aggregate(src_feats, ell_idx, ell_mask,
                                  interpret=interp)
    return ref.gnn_aggregate(src_feats, ell_idx, ell_mask)


def swa_attention_decode(q, k, v, kv_pos, kv_valid, q_pos, *, window,
                         use_pallas="auto"):
    use, interp = _resolve(use_pallas, "swa_attention_decode")
    if use:
        return _swa.swa_attention_decode(q, k, v, kv_pos, kv_valid, q_pos,
                                         window=window, interpret=interp)
    return ref.swa_attention_decode(q, k, v, kv_pos, kv_valid, q_pos,
                                    window)


def _np_quantize_int8(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Numpy mirror of ref.quantize_int8: the plain fp32 math (correctly
    rounded divide, round-half-even), which the device paths reproduce
    bit for bit (``ref.rint_div``)."""
    x = np.asarray(x, np.float32)
    absmax = np.max(np.abs(x), axis=1, keepdims=True) \
        if x.size else np.zeros((x.shape[0], 1), np.float32)
    scale = (absmax * np.float32(1.0 / 127.0)).astype(np.float32)
    safe = np.where(scale > 0, scale, np.float32(1.0)).astype(np.float32)
    q = np.clip(np.rint(x / safe), -127.0, 127.0).astype(np.int8)
    return q, scale


def _np_dequantize_int8(values: np.ndarray,
                        scales: np.ndarray) -> np.ndarray:
    return values.astype(np.float32) * scales.astype(np.float32)


def quantize_int8(x, *, use_pallas="auto"):
    """Per-row symmetric int8 quantize → (values int8, scales fp32 (n,1)).

    Host arrays off-TPU take a pure-numpy fast path: the exchange codec
    calls this per push/pull with delta-sized (varying-shape) batches,
    where eager jnp pays ~ms dispatch per call and jit would retrace
    per shape (see ROADMAP: device-resident codec path)."""
    use, interp = _resolve(use_pallas, "quantize_int8")
    if use:
        return _quant.quantize_int8(x, interpret=interp)
    if isinstance(x, np.ndarray):
        return _np_quantize_int8(x)
    return ref.quantize_int8(x)


def dequantize_int8(values, scales, *, use_pallas="auto"):
    use, interp = _resolve(use_pallas, "dequantize_int8")
    if use:
        return _quant.dequantize_int8(values, scales, interpret=interp)
    if isinstance(values, np.ndarray):
        return _np_dequantize_int8(values, np.asarray(scales))
    return ref.dequantize_int8(values, scales)


def _np_gather_quantize(table: np.ndarray, rows
                        ) -> tuple[np.ndarray, np.ndarray]:
    """Numpy fused gather+quantize (host tables): fancy-index then the
    op-for-op numpy encode — bit-identical to the device paths."""
    rows = np.asarray(rows, np.int64)
    return _np_quantize_int8(np.asarray(table, np.float32)[rows])


def _np_dequant_scatter(table: np.ndarray, rows, values, scales, *,
                        accumulate: bool = False) -> np.ndarray:
    """Numpy fused dequant+scatter.  Functional (returns a fresh table)
    to match the device paths — callers rebind."""
    out = np.array(table, np.float32, copy=True)
    rows = np.asarray(rows, np.int64)
    new = np.asarray(values).astype(np.float32) \
        * np.asarray(scales, np.float32)
    if accumulate:
        np.add.at(out, rows, new)
    else:
        out[rows] = new
    return out


def gather_quantize(table, rows, *, use_pallas="auto"):
    """Fused row-gather + int8 encode (pull responses): bit-identical to
    ``quantize_int8(table[rows])``.  Numpy tables take the numpy fused
    path; device tables run the jitted bucket-padded jnp twin on every
    backend (the Pallas body only when forced, see ``_COMPILES_ON_TPU``)."""
    use, interp = _resolve(use_pallas, "gather_quantize")
    if use:
        return _fused.gather_quantize(table, rows, interpret=interp)
    if isinstance(table, np.ndarray):
        return _np_gather_quantize(table, rows)
    return _fused.gather_quantize(table, rows, via="jnp")


def dequant_scatter(table, rows, values, scales, *, accumulate=False,
                    use_pallas="auto"):
    """Fused int8 decode + scatter-write/accumulate (push apply).
    Functional: returns the updated table; callers rebind.  Valid rows
    must be unique for ``accumulate=False``."""
    use, interp = _resolve(use_pallas, "dequant_scatter")
    if use:
        return _fused.dequant_scatter(table, rows, values, scales,
                                      accumulate=accumulate,
                                      interpret=interp)
    if isinstance(table, np.ndarray):
        return _np_dequant_scatter(table, rows, values, scales,
                                   accumulate=accumulate)
    return _fused.dequant_scatter(table, rows, values, scales,
                                  accumulate=accumulate, via="jnp")


def dequant_aggregate(src_values, src_scales, ell_idx, ell_mask, *,
                      use_pallas="auto"):
    """ELL mean-aggregation over an int8 source table, bit-identical to
    ``gnn_aggregate(dequantize_int8(values, scales), idx, mask)``.  The
    non-Pallas path routes to the jnp oracle (not a numpy mirror) so the
    reduction order matches :func:`gnn_aggregate`'s dispatch exactly."""
    use, interp = _resolve(use_pallas, "dequant_aggregate")
    if use:
        return _agg.dequant_aggregate(src_values, src_scales, ell_idx,
                                      ell_mask, interpret=interp)
    return ref.dequant_aggregate(jnp.asarray(src_values),
                                 jnp.asarray(src_scales),
                                 jnp.asarray(ell_idx),
                                 jnp.asarray(ell_mask))


def topk_mask(scores, k, *, use_pallas="auto"):
    use, interp = _resolve(use_pallas, "topk_mask")
    if use:
        return _topk.topk_mask(scores, k, interpret=interp)
    return ref.topk_mask(scores, k)


def ell_from_csr(indptr: np.ndarray, indices: np.ndarray, max_deg: int
                 ) -> tuple[np.ndarray, np.ndarray]:
    """CSR → ELL (idx, mask), truncating rows past ``max_deg`` (the
    sampler's fanout bound makes truncation a no-op in practice).

    Fully vectorized — a repeat/cumcount construction instead of the
    per-row python loop, which was O(V) interpreter time on the
    minibatch path for store-scale graphs."""
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices)
    n = len(indptr) - 1
    idx = np.zeros((n, max_deg), np.int32)
    mask = np.zeros((n, max_deg), bool)
    deg = np.minimum(np.diff(indptr), max_deg)
    rows = np.repeat(np.arange(n), deg)
    if rows.size:
        # cumcount: position of each kept entry within its row
        col = np.arange(rows.size) - np.repeat(np.cumsum(deg) - deg, deg)
        src = indices[np.repeat(indptr[:-1], deg) + col]
        idx[rows, col] = src
        mask[rows, col] = True
    return idx, mask
