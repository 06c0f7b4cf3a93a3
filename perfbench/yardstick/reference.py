"""Plain reference for one whole federated round of every client.

What it computes follows the published description: the configuration's
model (``models/<conv>.py``: its forward pass and a step's softmax
cross-entropy over the seed vertices), Adam (Kingma & Ba 2015), the
federated rules of OptimES (arXiv:2509.22922): a remote vertex's h^l
comes from its owner's pushed embedding (§3.2), pre-training uses only
local edges (§3.2.1), the push of a round is computed from the model
after epoch ε−1 (§4.2), and the server averages the clients' models
weighted by their training vertices (FedAvg).  The wire is per-row
symmetric int8 (scale = row absmax / 127, round half to even) with
error feedback: each push carries the previous push's rounding error of
that row (EF-SGD).  It imports nothing of the system under test.

Its inputs are the deployment's graph and features, the weights the
benchmark made from the seed, the partition, which in-edges each
client's shard retained and which edges the sampler drew.  Those last
three are choices, not values: :func:`check_shard` and
:func:`block_to_edges` hold them to the graph and to the sampler's and
the shard's rules.

Everything is float32; the int8 wire runs on the host.  ``mode="highest"`` runs every matrix product
at ``HIGHEST`` precision (the reference); ``mode="high"`` runs each as
three bfloat16 products with float32 sums, a_hi b_hi + a_hi b_lo +
a_lo b_hi, which is what ``Precision.HIGH`` does on a TPU, written out
so that it does the same on any backend (the control).  A model's
products go through :func:`mm`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def mm(a, b, mode: str):
    """float32 (a @ b) at the reference's or the control's precision."""
    if mode == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    if mode != "high":
        raise ValueError(f"unknown precision mode {mode!r}")
    (a_hi, a_lo), (b_hi, b_lo) = _split(a), _split(b)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


# -- seeds ---------------------------------------------------------------------

def key_from_seed(seed: int, salt: int) -> jax.Array:
    """A PRNG key from a seed of any size (jax keeps only 32 bits)."""
    word = np.random.default_rng([int(seed), int(salt)]).integers(2 ** 31)
    return jax.random.PRNGKey(int(word))


# -- int8 wire ------------------------------------------------------------------

def int8_roundtrip(x) -> np.ndarray:
    """Per-row symmetric int8 quantize then dequantize, on the host,
    where float32 division is correctly rounded."""
    x = np.asarray(x, np.float32)
    scale = np.abs(x).max(axis=-1, keepdims=True) / np.float32(127)
    safe = np.where(scale > 0, scale, np.float32(1))
    q = np.clip(np.rint(x / safe), -127, 127)
    return (q * scale).astype(np.float32)


#: how near, in int8 steps, a value must lie to a rounding midpoint for
#: its rounding side to count as the program's choice
TIE = 1e-3


def take_ties(x, stored, seen, have) -> np.ndarray:
    """``stored`` (= ``int8_roundtrip(x)``) with the program's rounding
    side taken where ``x`` lies within :data:`TIE` of a step's midpoint.

    Two float32 computations of the same row differ in their last bits,
    so where a value lies at a midpoint the two may round to adjacent
    steps; training from the other step then sends the rest of the round
    on another path.  The side the program took is a choice, like the
    sampler's edges: it is taken from ``seen`` (the rows the program
    pulled, where ``have``) only where it is one of the two steps beside
    the midpoint."""
    x = np.asarray(x, np.float32)
    scale = np.abs(x).max(axis=-1, keepdims=True) / np.float32(127)
    safe = np.where(scale > 0, scale, np.float32(1))
    q = x / safe
    lo = np.floor(q)
    level = np.rint(np.asarray(seen, np.float32) / safe)
    tie = (np.abs(q - lo - 0.5) < TIE) & have[:, None] \
        & ((level == lo) | (level == lo + 1))
    return np.where(tie, (level * scale).astype(np.float32), stored)


# -- the graph's rules ----------------------------------------------------------

class GraphIndex:
    """Look-ups the checks of the program's choices need, built once."""

    def __init__(self, indptr, indices, part, clients: int):
        self.indptr, self.indices, self.part = indptr, indices, part
        n = len(indptr) - 1
        self.n = n
        self.deg = np.diff(indptr)
        dst = np.repeat(np.arange(n, dtype=np.int64), self.deg)
        self.row_key = dst * n + indices.astype(np.int64)
        # in-edges of each vertex from each client's vertices
        self.in_from = np.stack([
            np.bincount(dst[part[indices] == c], minlength=n)
            for c in range(clients)])

    def edges_exist(self, es, ed) -> np.ndarray:
        """Whether each (es -> ed) is an in-edge of ed."""
        want = ed.astype(np.int64) * self.n + es.astype(np.int64)
        pos = np.minimum(np.searchsorted(self.row_key, want),
                         len(self.row_key) - 1)
        return self.row_key[pos] == want


def check_shard(gi: GraphIndex, e_src, e_dst, *, client, retention):
    """A client's expanded shard, as global-id edges, held to the rules:
    edges of the graph, each once, into the client's vertices; every
    vertex keeps all its local in-edges and min(retention, remote
    in-degree) remote ones.  Raises ``ValueError`` naming the rule."""
    if not np.all(gi.part[e_dst] == client):
        raise ValueError("a shard edge ends at another client's vertex")
    if not gi.edges_exist(e_src, e_dst).all():
        raise ValueError("a shard edge is not an edge of the graph")
    if len(np.unique(e_dst.astype(np.int64) * gi.n + e_src)) != len(e_src):
        raise ValueError("a shard edge appears twice")
    own = gi.part[e_src] == client
    mine = np.nonzero(gi.part == client)[0]
    n_local = np.bincount(e_dst[own], minlength=gi.n)[mine]
    n_remote = np.bincount(e_dst[~own], minlength=gi.n)[mine]
    local_in = gi.in_from[client][mine]
    if not np.array_equal(n_local, local_in):
        raise ValueError("a shard vertex lost some of its local in-edges")
    if not np.array_equal(n_remote, np.minimum(gi.deg[mine] - local_in,
                                               retention)):
        raise ValueError("a shard vertex keeps other than min(retention, "
                         "remote in-degree) remote in-edges")


def block_to_edges(batch, global_ids, *, client, gi: GraphIndex, fanout,
                   retention, train_mask):
    """The sampled computation graph in global ids, checked against the
    graph and the sampler's rules.

    ``batch`` is one minibatch as host arrays: ``input_ids`` (the hop-L
    vertex list, whose prefixes are the shallower hops' lists),
    ``seeds``/``seed_mask``, and per GNN layer (``blocks[0]`` reads hop
    L) the padded ``edge_src``/``edge_dst`` positions, ``edge_mask`` and
    ``dst_mask``.  ``global_ids`` maps the client's vertex numbers to
    the graph's.

    Returns ``(layers, seed_gids)`` where ``layers[l]`` holds the src
    and dst vertex lists and the edge list of GNN layer ``l + 1``, or
    raises ``ValueError`` naming the broken rule."""
    part = gi.part
    blocks = batch["blocks"]
    hop_l = global_ids[np.asarray(batch["input_ids"])]
    n_seed = int(np.sum(batch["seed_mask"]))
    seed_gids = global_ids[np.asarray(batch["seeds"])[:n_seed]]
    if len(np.unique(seed_gids)) != n_seed:
        raise ValueError("a seed vertex repeats in one batch")
    if not (np.all(part[seed_gids] == client)
            and np.all(train_mask[seed_gids])):
        raise ValueError("a seed is not a training vertex of its client")
    layers = []
    n_src = None
    for l, blk in enumerate(blocks, start=1):
        m = np.asarray(blk["edge_mask"], bool)
        e_src_pos = np.asarray(blk["edge_src"])[m]
        n_dst = int(np.sum(blk["dst_mask"]))
        if n_src is None:     # hop L: as far as any edge or self row reads
            n_src = max(n_dst, int(e_src_pos.max()) + 1 if len(e_src_pos)
                        else 0)
        src = hop_l[:n_src]
        dst = hop_l[:n_dst]
        if len(np.unique(src)) != len(src):
            raise ValueError(f"layer {l}: a vertex repeats in its inputs")
        es = src[e_src_pos]
        ed = dst[np.asarray(blk["edge_dst"])[m]]
        ok = gi.edges_exist(es, ed)
        if not ok.all():
            raise ValueError(f"layer {l}: {int((~ok).sum())} sampled edges "
                             "are not edges of the graph")
        if len(np.unique(ed.astype(np.int64) * gi.n + es)) != len(es):
            raise ValueError(f"layer {l}: an edge is drawn twice")
        local_dst = part[dst] == client
        # fanout rule: a local vertex draws min(fanout, eligible) of its
        # in-edges; at hop L only local sources are eligible, elsewhere
        # local sources plus at most `retention` remote ones
        own = np.bincount(_positions(dst, ed), minlength=n_dst)
        nbr_local = gi.in_from[client][dst]
        if l == 1:
            eligible = nbr_local
            if np.any(part[es] != client):
                raise ValueError("layer 1 reads a remote vertex's features")
        else:
            eligible = nbr_local + np.minimum(gi.deg[dst] - nbr_local,
                                              retention)
        want = np.where(local_dst, np.minimum(fanout, eligible), 0)
        if not np.array_equal(own, want):
            raise ValueError(f"layer {l}: per-vertex draw counts differ from "
                             "min(fanout, eligible in-edges)")
        layers.append({"src": src, "dst": dst, "e_src": es, "e_dst": ed})
        n_src = n_dst
    if not np.array_equal(layers[-1]["dst"], seed_gids):
        raise ValueError("the last layer's outputs are not the seeds")
    return layers, seed_gids


def _positions(nodes: np.ndarray, gids: np.ndarray) -> np.ndarray:
    """Index of each of ``gids`` in the distinct list ``nodes``."""
    order = np.argsort(nodes, kind="stable")
    pos = np.searchsorted(nodes[order], gids)
    pos = np.minimum(pos, len(nodes) - 1)
    if not np.array_equal(nodes[order][pos], gids):
        raise ValueError("an edge endpoint is not in its layer's vertex list")
    return order[pos]


def _pow2(n: int) -> int:
    return 1 << max(4, int(n - 1).bit_length())


# -- whole-graph propagation (pre-training, push, evaluation) ---------------------

def _pad_edges(e_src, e_dst, *masks):
    n = _pow2(len(e_src))
    out = [np.zeros(n, np.int32), np.zeros(n, np.int32)]
    out[0][: len(e_src)] = e_src
    out[1][: len(e_dst)] = e_dst
    for m in masks:
        p = np.zeros(n, np.float32)
        p[: len(m)] = m
        out.append(p)
    return [jnp.asarray(a) for a in out]


def pretrain_h(propagate, params, features, gi: GraphIndex, *, mode):
    """h^1..h^{L-1} of every vertex over its own client's edges only, as
    each owner computes it before round 0."""
    dst = np.repeat(np.arange(gi.n), gi.deg)
    keep = (gi.part[gi.indices] == gi.part[dst]).astype(np.float32)
    e = _pad_edges(gi.indices, dst, keep, keep)
    outs = propagate(params, jnp.asarray(features), *e, None, None,
                     mode=mode)
    return outs[:-1]


def client_h(propagate, params, features, gi: GraphIndex, e_src, e_dst,
             tables, *, client, mode):
    """h^1..h^{L-1} of a client's vertices over its expanded shard: at
    layer 1 only local sources (a remote vertex's features are private),
    above it the retained remote sources with their pulled rows."""
    own = gi.part == client
    e = _pad_edges(e_src, e_dst, own[e_src].astype(np.float32),
                   np.ones(len(e_src), np.float32))
    outs = propagate(params, jnp.asarray(features), *e, jnp.asarray(own),
                     list(tables), mode=mode)
    return outs[:-1]


def eval_vertices(indptr, max_edges: int, seed: int) -> np.ndarray:
    """The aggregation server's held-out graph: the whole graph, or past
    ``max_edges`` in-edges a seeded uniform vertex sample whose in-edges
    fit the budget (a permutation prefix of the trainer's seed)."""
    deg = np.diff(np.asarray(indptr))
    if int(deg.sum()) <= max_edges:
        return np.arange(len(deg), dtype=np.int64)
    perm = np.random.default_rng((seed, 104729)).permutation(len(deg))
    k = int(np.searchsorted(np.cumsum(deg[perm]), max_edges, side="right"))
    return np.sort(perm[: max(1, k)]).astype(np.int64)


def accuracy(propagate, params, features, labels, train_mask,
             gi: GraphIndex, sel: np.ndarray, *, mode) -> float:
    """Test accuracy of the model's full-neighbourhood ``propagate`` over
    the subgraph induced by ``sel``, on its vertices outside the training
    set."""
    dst = np.repeat(np.arange(gi.n), gi.deg)
    inside = np.zeros(gi.n, bool)
    inside[sel] = True
    keep = inside[gi.indices] & inside[dst]
    pos = np.full(gi.n, -1, np.int64)
    pos[sel] = np.arange(len(sel))
    e = _pad_edges(pos[gi.indices[keep]], pos[dst[keep]],
                   np.ones(int(keep.sum()), np.float32),
                   np.ones(int(keep.sum()), np.float32))
    outs = propagate(params, jnp.asarray(features[sel]), *e, None, None,
                     mode=mode)
    pred = np.asarray(jnp.argmax(outs[-1], axis=-1))
    test = ~np.asarray(train_mask[sel], bool)
    return float((pred[test] == labels[sel][test]).mean())


# -- the local steps -------------------------------------------------------------

def pack_batch(layers, seed_gids, *, labels, part, client):
    """One checked minibatch as vertex ids and positions (unpadded)."""
    packed = []
    for lay in layers:
        packed.append({
            "e_src": _positions(lay["src"], lay["e_src"]),
            "e_dst": _positions(lay["dst"], lay["e_dst"]),
            "self": _positions(lay["src"], lay["dst"]),
            "remote": part[lay["dst"]] != client,
            "gid": lay["dst"]})
    return {"x": layers[0]["src"], "layers": packed,
            "labels": labels[seed_gids],
            "mask": np.ones(len(seed_gids), np.float32)}


def stack_batches(batches) -> dict:
    """Packed batches padded to shared power-of-two sizes and stacked, one
    row per step."""
    def pad(a, n, dtype):
        out = np.zeros(n, dtype)
        out[: len(a)] = a
        return out

    L = len(batches[0]["layers"])
    n_x = _pow2(max(len(b["x"]) for b in batches))
    n_y = _pow2(max(len(b["labels"]) for b in batches))
    sizes = [(_pow2(max(len(b["layers"][l]["gid"]) for b in batches)),
              _pow2(max(len(b["layers"][l]["e_src"]) for b in batches)))
             for l in range(L)]
    out = {"x": np.stack([pad(b["x"], n_x, np.int32) for b in batches]),
           "labels": np.stack([pad(b["labels"], n_y, np.int32)
                               for b in batches]),
           "mask": np.stack([pad(b["mask"], n_y, np.float32)
                             for b in batches]),
           "layers": []}
    for l, (n_d, n_e) in enumerate(sizes):
        lays = [b["layers"][l] for b in batches]
        out["layers"].append({
            "e_src": np.stack([pad(y["e_src"], n_e, np.int32) for y in lays]),
            "e_dst": np.stack([pad(y["e_dst"], n_e, np.int32) for y in lays]),
            "e_w": np.stack([pad(np.ones(len(y["e_src"])), n_e, np.float32)
                             for y in lays]),
            "self": np.stack([pad(y["self"], n_d, np.int32) for y in lays]),
            "remote": np.stack([pad(y["remote"], n_d, bool) for y in lays]),
            "gid": np.stack([pad(y["gid"], n_d, np.int32) for y in lays])})
    return out


@functools.partial(jax.jit,
                   static_argnames=("loss", "mode", "lr", "b1", "b2", "eps"))
def local_round(params, stacked, features, tables, *, loss, mode, lr, b1, b2,
                eps):
    """Adam from ``params`` over the stacked steps of the model's
    ``loss``.  Returns per step the loss, the gradient and the weights
    after it."""
    def step(carry, b):
        p, mu, nu, t = carry
        value, g = jax.value_and_grad(loss)(p, b, features, tables, mode)
        t = t + 1.0
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    nu, g)
        p = jax.tree_util.tree_map(
            lambda q, m, v: q - lr * (m / (1 - b1 ** t))
            / (jnp.sqrt(v / (1 - b2 ** t)) + eps), p, mu, nu)
        return (p, mu, nu, t), (value, g, p)

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    _, (losses, grads, ps) = jax.lax.scan(
        step, (params, zeros, zeros, jnp.float32(0.0)), stacked)
    return losses, grads, ps
