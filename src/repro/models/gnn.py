"""GraphConv and SAGEConv GNNs in pure JAX over padded sampler blocks.

The forward pass mirrors the paper's §3.2.2: layer ``l`` consumes the
``h^{l-1}`` embeddings of the nodes at hop ``L-(l-1)`` and produces
``h^l`` at hop ``L-l``; rows belonging to *remote* destination nodes are
overwritten from the client's pulled embedding cache instead of being
computed (their neighbourhoods live on other clients).

Everything is functional: parameters are pytrees, blocks are dicts of
padded arrays, and the train step is a single jitted function per
(shard, batch-size) shape signature.  Two functions build those dicts:
:func:`blocks_to_arrays` one minibatch at a time, and
:func:`epoch_to_arrays` a whole epoch's minibatches, packed into one
int32 host array, moved in one host→device transfer and split on the
device into the same per-step dicts.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.sampler import MiniBatch
from repro.obsv.metrics import REGISTRY

Params = Any

#: host→device transfers made for training-step inputs, and the
#: minibatches those transfers carried
_TRANSFERS = REGISTRY.counter("train.step_input_transfers")
_STEPS = REGISTRY.counter("train.step_input_steps")


# -- parameter init ------------------------------------------------------

def init_gnn(
    rng: jax.Array,
    conv: str,
    in_dim: int,
    hidden: int,
    out_dim: int,
    num_layers: int,
) -> Params:
    """Initialise an L-layer GNN.  ``conv`` ∈ {graphconv, sageconv}."""
    assert conv in ("graphconv", "sageconv"), conv
    dims = [in_dim] + [hidden] * (num_layers - 1) + [out_dim]
    layers = []
    for l in range(num_layers):
        rng, k1, k2 = jax.random.split(rng, 3)
        d_in, d_out = dims[l], dims[l + 1]
        scale = jnp.sqrt(2.0 / d_in)
        layer = {"w_neigh": jax.random.normal(k1, (d_in, d_out)) * scale,
                 "b": jnp.zeros((d_out,))}
        if conv == "sageconv":
            layer["w_self"] = jax.random.normal(k2, (d_in, d_out)) * scale
        layers.append(layer)
    # ``conv`` is static (a string) — callers pass it to forward/train_step
    # explicitly so the param pytree stays jit-able.
    return layers


# -- blocks as jit-able pytrees -------------------------------------------

def blocks_to_arrays(mb: MiniBatch) -> dict:
    """Convert a sampled :class:`MiniBatch` to a pytree of arrays."""
    return {
        "blocks": [
            {
                "edge_src": jnp.asarray(b.edge_src, jnp.int32),
                "edge_dst": jnp.asarray(b.edge_dst, jnp.int32),
                "edge_mask": jnp.asarray(b.edge_mask),
                "dst_remote_mask": jnp.asarray(b.dst_remote_mask),
                "dst_remote_slot": jnp.asarray(b.dst_remote_slot, jnp.int32),
                "dst_mask": jnp.asarray(b.dst_mask),
            }
            for b in mb.blocks
        ],
        "input_ids": jnp.asarray(mb.input_ids, jnp.int32),
        "seed_mask": jnp.asarray(mb.seed_mask),
        "seeds": jnp.asarray(mb.seeds, jnp.int32),
    }


_BLOCK_KEYS = ("edge_src", "edge_dst", "edge_mask", "dst_remote_mask",
               "dst_remote_slot", "dst_mask")
_BATCH_KEYS = ("input_ids", "seed_mask", "seeds")


def _step_arrays(mb: MiniBatch):
    """``(block, key, host array)`` for every array of a step, in packing
    order; ``block`` is None for the batch-level arrays."""
    for i, b in enumerate(mb.blocks):
        for k in _BLOCK_KEYS:
            yield i, k, getattr(b, k)
    for k in _BATCH_KEYS:
        yield None, k, getattr(mb, k)


def pack_epoch(batches: Sequence[MiniBatch]) -> tuple[np.ndarray, tuple]:
    """An epoch's minibatches as one ``(len(batches), W)`` int32 array,
    one row a step, and its layout: ``(block, key, offset, shape,
    is_bool)`` for each array of a step.  Bool masks become 0/1 and ids
    int32.  The layout is read from the first minibatch; a sampler pads
    every minibatch to the same static shapes, and an array of another
    shape raises ``ValueError``."""
    layout, off = [], 0
    for blk, k, a in _step_arrays(batches[0]):
        layout.append((blk, k, off, a.shape, a.dtype == np.bool_))
        off += a.size
    packed = np.empty((len(batches), off), np.int32)
    for row, mb in zip(packed, batches):
        for (_, k, o, shape, _), (_, _, a) in zip(layout, _step_arrays(mb)):
            if a.shape != shape:
                raise ValueError(f"{k}: shape {a.shape} in an epoch laid "
                                 f"out for {shape}")
            row[o: o + a.size] = a.reshape(-1)
    return packed, tuple(layout)


@functools.partial(jax.jit, static_argnames=("layout",))
def _split_epoch(packed, *, layout):
    """The rows of :func:`pack_epoch` as per-step batch dicts, in one
    program."""
    n_blocks = 1 + max(blk for blk, *_ in layout if blk is not None)
    steps = []
    for i in range(packed.shape[0]):
        blocks = [{} for _ in range(n_blocks)]
        batch = {"blocks": blocks}
        for blk, k, off, shape, is_bool in layout:
            x = packed[i, off: off + math.prod(shape)].reshape(shape)
            (batch if blk is None else blocks[blk])[k] = \
                x != 0 if is_bool else x
        steps.append(batch)
    return steps


def epoch_to_arrays(batches: Sequence[MiniBatch]) -> list[dict]:
    """Every step's batch dict of an epoch, each equal to
    ``blocks_to_arrays(mb)`` in keys, shapes, dtypes and values, moved
    to the device in one transfer instead of one for each array of each
    step."""
    if not batches:
        return []
    packed, layout = pack_epoch(batches)
    steps = _split_epoch(jax.device_put(packed), layout=layout)
    _TRANSFERS.inc()
    _STEPS.inc(len(batches))
    return steps


def _segment_mean(vals, seg_ids, mask, num_segments):
    w = mask.astype(vals.dtype)
    summed = jax.ops.segment_sum(vals * w[:, None], seg_ids,
                                 num_segments=num_segments)
    cnt = jax.ops.segment_sum(w, seg_ids, num_segments=num_segments)
    return summed / jnp.maximum(cnt, 1.0)[:, None], cnt


def _layer_forward(layer, conv, h_src, blk, *, last: bool):
    n_dst = blk["dst_remote_mask"].shape[0]   # static padded dst size
    gathered = h_src[blk["edge_src"]]
    agg, cnt = _segment_mean(gathered, blk["edge_dst"], blk["edge_mask"], n_dst)
    h_self = h_src[:n_dst]
    if conv == "graphconv":
        # mean over N(u) ∪ {u} (right-normalised GCN over sampled blocks)
        mixed = (agg * cnt[:, None] + h_self) / (cnt[:, None] + 1.0)
        out = mixed @ layer["w_neigh"] + layer["b"]
    else:  # sageconv (mean aggregator)
        out = h_self @ layer["w_self"] + agg @ layer["w_neigh"] + layer["b"]
    if not last:
        out = jax.nn.relu(out)
    return out


def forward(
    params: Params,
    batch: dict,
    features: jax.Array,           # (num_local, F) shard feature table
    caches: Sequence[jax.Array],   # L-1 tables (num_remote_pad, hidden)
    *,
    conv: str,
) -> jax.Array:
    """Returns logits for the (padded) seed set."""
    layers = params
    L = len(layers)
    h = features[batch["input_ids"]]        # hop-L nodes are all local
    for l, (layer, blk) in enumerate(zip(layers, batch["blocks"]), start=1):
        out = _layer_forward(layer, conv, h, blk, last=(l == L))
        if l < L:
            # remote dst rows are served from the h^l cache, not computed
            cached = caches[l - 1][blk["dst_remote_slot"]]
            out = jnp.where(blk["dst_remote_mask"][:, None], cached, out)
        h = out
    return h


def loss_fn(params, batch, features, caches, labels, *, conv):
    logits = forward(params, batch, features, caches, conv=conv)
    seed_labels = labels[batch["seeds"]]
    mask = batch["seed_mask"].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, seed_labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)


@functools.partial(jax.jit, static_argnames=("lr", "conv"))
def sgd_train_step(params, batch, features, caches, labels, *, lr: float,
                   conv: str):
    loss, grads = jax.value_and_grad(
        functools.partial(loss_fn, conv=conv))(params, batch, features,
                                               caches, labels)
    new_params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return new_params, loss


@functools.partial(jax.jit, static_argnames=("conv",))
def predict(params, batch, features, caches, *, conv):
    logits = forward(params, batch, features, caches, conv=conv)
    return jnp.argmax(logits, axis=-1)


# -- full-shard propagation for push / evaluation --------------------------

def full_propagate(
    params: Params,
    shard_arrays: dict,
    caches: Sequence[jax.Array] | None,
    *,
    conv: str,
) -> list[jax.Array]:
    """Compute h^1..h^L for ALL local vertices of a shard.

    Used (a) to produce push-node embeddings after a round, (b) in the
    pre-training bootstrap (``caches=None`` ⇒ remote neighbours masked,
    matching §3.2.1), and (c) for full-graph evaluation.

    ``shard_arrays`` holds the shard CSR flattened to an edge list:
      edge_src (E,), edge_dst (E,), src_is_remote (E,), num_local,
      features (num_local, F).
    Returns list of per-layer local embeddings [h^1, ..., h^L].
    """
    layers = params
    L = len(layers)
    num_local = shard_arrays["num_local"]
    e_src = shard_arrays["edge_src"]
    e_dst = shard_arrays["edge_dst"]
    remote_e = shard_arrays["src_is_remote"]

    h_local = shard_arrays["features"]
    outs = []
    for l, layer in enumerate(layers, start=1):
        if l == 1 or caches is None:
            # remote sources contribute nothing (h^0 private / no cache)
            mask = ~remote_e
            cache_tbl = jnp.zeros((1, h_local.shape[1]), h_local.dtype)
            src_tbl = jnp.concatenate([h_local, cache_tbl], axis=0)
            src_idx = jnp.where(remote_e, num_local, e_src)
        else:
            mask = jnp.ones_like(remote_e)
            src_tbl = jnp.concatenate([h_local, caches[l - 2]], axis=0)
            src_idx = e_src  # remote ids already offset past num_local
        gathered = src_tbl[src_idx]
        agg, cnt = _segment_mean(gathered, e_dst, mask, num_local)
        if conv == "graphconv":
            mixed = (agg * cnt[:, None] + h_local) / (cnt[:, None] + 1.0)
            out = mixed @ layer["w_neigh"] + layer["b"]
        else:
            out = h_local @ layer["w_self"] + agg @ layer["w_neigh"] + layer["b"]
        if l < L:
            out = jax.nn.relu(out)
        h_local = out
        outs.append(out)
    return outs


def shard_to_arrays(shard) -> dict:
    """Flatten a ClientShard's CSR (local destinations) to jit inputs."""
    e_dst = np.repeat(np.arange(shard.num_local), np.diff(shard.indptr))
    e_src = shard.indices.astype(np.int64)
    remote = e_src >= shard.num_local
    return {
        # remote src ids are already offset past num_local, which is where
        # full_propagate concatenates the cache table — no remap needed.
        "edge_src": jnp.asarray(e_src, jnp.int32),
        "edge_dst": jnp.asarray(e_dst, jnp.int32),
        "src_is_remote": jnp.asarray(remote),
        "num_local": shard.num_local,
        "features": jnp.asarray(shard.features, jnp.float32),
    }
