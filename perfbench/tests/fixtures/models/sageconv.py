"""GraphSAGE with the mean aggregator (Hamilton et al. 2017), as the
program's ``sageconv``: h_v W_self + mean over N(v) of h_u W_neigh + b,
ReLU between layers.  No cell runs it: the seam test points the model
lookup here to show that a model comes in as one module of the roles
``yardstick/models/graphconv.py`` lists."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.yardstick.flops import hop_sizes
from perfbench.yardstick.reference import mm

#: the self term h_v W_self; the seam test drops it to plant a fault
SELF_TERM = True


def program_args(cfg: dict) -> dict:
    """The trainer's shape arguments, and ``root_weight`` (PyG's name
    for the self term), which the program's trainer does not take: the
    seam tests record it on its way into the trainer and take it out
    before the real one."""
    return {"conv": cfg["conv"], "num_layers": int(cfg["layers"]),
            "hidden": int(cfg["hidden"]), "root_weight": SELF_TERM}


def widths(cfg: dict) -> tuple[int, ...]:
    L = int(cfg["layers"])
    return (int(cfg["features"]),) + (int(cfg["hidden"]),) * (L - 1) \
        + (int(cfg["classes"]),)


def exchanged_widths(cfg: dict) -> tuple[int, ...]:
    return (int(cfg["hidden"]),) * (int(cfg["layers"]) - 1)


@functools.partial(jax.jit, static_argnames=("dims",))
def _init(key, dims: tuple[int, ...]):
    out = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        key, k_self, k_neigh = jax.random.split(key, 3)
        scale = jnp.sqrt(2.0 / d_in)
        out.append((jax.random.normal(k_self, (d_in, d_out)) * scale,
                    jax.random.normal(k_neigh, (d_in, d_out)) * scale,
                    jnp.zeros((d_out,), jnp.float32)))
    return out


def init(key, cfg: dict):
    return _init(key, widths(cfg))


def to_program(leaves) -> list[dict]:
    return [{"w_self": ws, "w_neigh": wn, "b": b} for ws, wn, b in leaves]


def from_program(params) -> list[tuple]:
    return [(p["w_self"], p["w_neigh"], p["b"]) for p in params]


def _layer(h_self, mean, ws, wn, b, mode):
    out = mm(mean, wn, mode)
    if SELF_TERM:
        out = mm(h_self, ws, mode) + out
    return out + b


@functools.partial(jax.jit, static_argnames=("mode",))
def propagate(params, x, e_src, e_dst, w_first, w_rest, own, tables, *,
              mode):
    n = x.shape[0]
    L = len(params)
    h, outs = x, []
    for l, (ws, wn, b) in enumerate(params, start=1):
        wt = w_first if l == 1 else w_rest
        src = h
        if l > 1 and tables is not None:
            src = jnp.where(own[:, None], h, tables[l - 2])
        agg = jax.ops.segment_sum(src[e_src] * wt[:, None], e_dst,
                                  num_segments=n)
        cnt = jax.ops.segment_sum(wt, e_dst, num_segments=n)
        h = _layer(h, agg / jnp.maximum(cnt, 1)[:, None], ws, wn, b, mode)
        if l < L:
            h = jax.nn.relu(h)
        outs.append(h)
    return outs


def loss(params, b, features, tables, mode):
    h = features[b["x"]]
    L = len(params)
    for l, ((ws, wn, bias), lay) in enumerate(zip(params, b["layers"]),
                                              start=1):
        n_dst = lay["self"].shape[0]
        e_w = lay["e_w"]
        agg = jax.ops.segment_sum(h[lay["e_src"]] * e_w[:, None],
                                  lay["e_dst"], num_segments=n_dst)
        cnt = jax.ops.segment_sum(e_w, lay["e_dst"], num_segments=n_dst)
        out = _layer(h[lay["self"]], agg / jnp.maximum(cnt, 1)[:, None],
                     ws, wn, bias, mode)
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


def step_flops(cfg: dict, shard_vertices: int) -> float:
    """Two (d_in x d_out) products a layer, each three times with the
    backward pass (twice at layer 1); the mean over at most ``fanout``
    rows twice."""
    dims = widths(cfg)
    L = len(dims) - 1
    fanout = int(cfg["fanout"])
    hops = hop_sizes(int(cfg["batch"]), fanout, L, shard_vertices)
    total = 0.0
    for l in range(1, L + 1):
        n_dst = hops[L - l]
        matmul = 2 * 2.0 * n_dst * dims[l - 1] * dims[l]
        total += matmul * (2 if l == 1 else 3) \
            + 2 * n_dst * (fanout + 1) * dims[l - 1]
    return total
