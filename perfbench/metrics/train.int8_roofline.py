"""Roofline share of the int8 wire kernels (``kernels/quantize.py``), %:
the HBM bytes the round's encodes and decodes need (pushed and pulled
rows at each width of the model module's ``exchanged_widths``,
``yardstick/models/<conv>.py``; bytes a row by ``yardstick/flops.py``)
over the device time of the kernels' programs (``jit_quantize_padded``,
``jit_dequantize_padded``) in the trace, against the chip's HBM
bandwidth.  Memory-bound, so bytes set the roofline."""


def read(ctx):
    dev = ctx["device"]
    if dev is None:
        return None
    kernel_s = sum(v for name, v in dev["modules"].items()
                   if "quantize_padded" in name)
    if kernel_s <= 0:
        return None
    need_s = ctx["work"]["codec_bytes_per_round"] * len(ctx["rounds"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need_s / kernel_s
