"""GraphConv (Kipf & Welling 2017): the model of a configuration whose
``conv`` is ``graphconv``.  Each layer takes the mean over N(v) and v
itself, then a dense layer; ReLU between layers.

A model module holds all of the benchmark's code that depends on the
architecture; ``perfbench/harness.py`` finds it by the configuration's
``conv`` (``yardstick/models/<conv>.py``).  Its roles:

  program_args(cfg)       the keyword arguments that set the model's
                          shape in the program's ``FederatedGNNTrainer``
  widths(cfg)             input width, then each layer's output width
  exchanged_widths(cfg)   the width of each exchanged h^1..h^{L-1}
  init(key, cfg)          seeded weights, one tuple of leaves per layer
  to_program(leaves)      those tuples as the program's params (also
                          Adam's moments and the average)
  from_program(params)    and back
  propagate(...)          h^1..h^L of every vertex: the pre-training
                          push, the round's push and evaluation
  loss(...)               one sampled step's loss, remote rows from the
                          pulled tables
  step_flops(cfg, n)      model FLOPs of one training step

Like the rest of the yardstick it imports nothing of the system under
test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.yardstick.flops import hop_sizes
from perfbench.yardstick.reference import mm


def program_args(cfg: dict) -> dict:
    return {"conv": cfg["conv"], "num_layers": int(cfg["layers"]),
            "hidden": int(cfg["hidden"])}


def widths(cfg: dict) -> tuple[int, ...]:
    L = int(cfg["layers"])
    return (int(cfg["features"]),) + (int(cfg["hidden"]),) * (L - 1) \
        + (int(cfg["classes"]),)


def exchanged_widths(cfg: dict) -> tuple[int, ...]:
    return (int(cfg["hidden"]),) * (int(cfg["layers"]) - 1)


@functools.partial(jax.jit, static_argnames=("dims",))
def init_params(key, dims: tuple[int, ...]):
    """He-normal dense weights and zero biases, one (W, b) per layer, made
    on the device in one call."""
    out = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (d_in, d_out), jnp.float32) \
            * jnp.sqrt(2.0 / d_in)
        out.append((w, jnp.zeros((d_out,), jnp.float32)))
    return out


def init(key, cfg: dict):
    return init_params(key, widths(cfg))


def to_program(leaves) -> list[dict]:
    return [{"w_neigh": w, "b": b} for w, b in leaves]


def from_program(params) -> list[tuple]:
    return [(p["w_neigh"], p["b"]) for p in params]


@functools.partial(jax.jit, static_argnames=("mode",))
def propagate(params, x, e_src, e_dst, w_first, w_rest, own, tables, *,
              mode):
    """h^1..h^L of every vertex.  An edge's weight is ``w_first`` at
    layer 1 and ``w_rest`` above; above layer 1 a source the client does
    not own (``own`` false) reads its row from ``tables[l - 2]``.  Each
    layer multiplies before it aggregates, ((sum h_u + h_v) W) = (sum
    h_u W + h_v W): the same sum, so that the edge gather is hidden-wide
    and not feature-wide."""
    n = x.shape[0]
    L = len(params)
    h, outs = x, []
    for l, (w, b) in enumerate(params, start=1):
        z = mm(h, w, mode)
        wt = w_first if l == 1 else w_rest
        src = z
        if l > 1 and tables is not None:
            src = jnp.where(own[:, None], z, mm(tables[l - 2], w, mode))
        agg = jax.ops.segment_sum(src[e_src] * wt[:, None], e_dst,
                                  num_segments=n)
        cnt = jax.ops.segment_sum(wt, e_dst, num_segments=n)
        h = (agg + z) / (cnt[:, None] + 1) + b
        if l < L:
            h = jax.nn.relu(h)
        outs.append(h)
    return outs


def loss(params, b, features, tables, mode):
    h = features[b["x"]]
    L = len(params)
    for l, ((w, bias), lay) in enumerate(zip(params, b["layers"]), start=1):
        n_dst = lay["self"].shape[0]
        e_w = lay["e_w"]
        agg = jax.ops.segment_sum(h[lay["e_src"]] * e_w[:, None],
                                  lay["e_dst"], num_segments=n_dst)
        cnt = jax.ops.segment_sum(e_w, lay["e_dst"], num_segments=n_dst)
        mixed = (agg + h[lay["self"]]) / (cnt[:, None] + 1)
        out = mm(mixed, w, mode) + bias
        if l < L:
            out = jax.nn.relu(out)
            out = jnp.where(lay["remote"][:, None], tables[l - 1][lay["gid"]],
                            out)
        h = out
    n_seed = b["labels"].shape[0]
    logp = jax.nn.log_softmax(h[:n_seed], axis=-1)
    nll = -jnp.take_along_axis(logp, b["labels"][:, None], axis=-1)[:, 0]
    mask = b["mask"]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


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


def step_flops(cfg: dict, shard_vertices: int) -> float:
    return train_step_flops(batch=int(cfg["batch"]),
                            fanout=int(cfg["fanout"]),
                            widths=list(widths(cfg)),
                            shard_vertices=shard_vertices)
