"""Operations and bytes the algorithm needs, from a configuration's shapes.

Kept with the benchmark so that no change to the program can change
what a roofline or utilization is measured against.
"""

from __future__ import annotations


def hop_sizes(batch: int, fanout: int, layers: int, shard_vertices: int
              ) -> list[int]:
    """Vertices at hop h = 0..L of a sampled batch: B (f+1)^h, capped by
    the vertices the client can reach (its own plus its pulled ones),
    as the sampler's pads are."""
    return [min(batch * (fanout + 1) ** h, shard_vertices)
            for h in range(layers + 1)]


def train_step_flops(*, batch: int, fanout: int, widths: list[int],
                     shard_vertices: int) -> float:
    """Model FLOPs of one GraphConv training step on a full batch.

    ``widths`` = [features, hidden, ..., classes].  GNN layer l
    (1-based, L layers) maps hop L-l+1 to hop L-l: each of its n_dst
    outputs sums at most ``fanout`` neighbour rows and its own row
    (d_in adds each, then a scale), then one (d_in x d_out) product.
    The backward pass costs the product twice (weight and input
    gradients) except at layer 1, whose input, the features, needs no
    gradient; the aggregation once more."""
    L = len(widths) - 1
    hops = hop_sizes(batch, fanout, L, shard_vertices)
    total = 0.0
    for l in range(1, L + 1):
        n_dst = hops[L - l]
        d_in, d_out = widths[l - 1], widths[l]
        matmul = 2.0 * n_dst * d_in * d_out
        agg = n_dst * (fanout + 2) * d_in
        total += matmul * (2 if l == 1 else 3) + 2 * agg
    return total


def int8_codec_bytes(rows: int, hidden: int) -> float:
    """HBM bytes of one int8 wire crossing of ``rows`` rows of width
    ``hidden``: quantize reads 4h and writes h + 4 per row, dequantize
    reads h + 4 and writes 4h."""
    return float(rows) * 2.0 * (5.0 * hidden + 4.0)
