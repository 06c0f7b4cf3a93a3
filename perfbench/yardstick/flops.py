"""Operations and bytes the algorithm needs, from a configuration's shapes.

Kept with the benchmark so that no change to the program can change
what a roofline or utilization is measured against.  A model's FLOPs
per step are its module's (``models/<conv>.py``, ``step_flops``).
"""

from __future__ import annotations


def hop_sizes(batch: int, fanout: int, layers: int, shard_vertices: int
              ) -> list[int]:
    """Vertices at hop h = 0..L of a sampled batch: B (f+1)^h, capped by
    the vertices the client can reach (its own plus its pulled ones),
    as the sampler's pads are."""
    return [min(batch * (fanout + 1) ** h, shard_vertices)
            for h in range(layers + 1)]


def int8_codec_bytes(rows: int, hidden: int) -> float:
    """HBM bytes of one int8 wire crossing of ``rows`` rows of width
    ``hidden``: quantize reads 4h and writes h + 4 per row, dequantize
    reads h + 4 and writes 4h."""
    return float(rows) * 2.0 * (5.0 * hidden + 4.0)
