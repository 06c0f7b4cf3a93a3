"""Mini-batch neighbourhood sampler with federated boundary rules.

Builds DGL-style bipartite *blocks* for an L-layer GNN, enforcing the
paper's §3.2.2 custom-sampler rules:

  (1) only LOCAL vertices are sampled at the root level;
  (2) a remote vertex sampled at hop l ≤ L-1 terminates its path (its
      neighbourhood lives on another client);
  (3) no remote vertices appear at the L-th hop (their h^0 features are
      unavailable at the embedding server for privacy).

Blocks are padded to static shapes so the JAX training step compiles
once per (shard, batch size).  Remote destination nodes are *not*
computed by the GNN layer — the runtime overwrites their rows from the
client's local embedding cache (h^l pulled from the embedding server).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

from repro.obsv.metrics import REGISTRY

from .partition import ClientShard

#: local frontier vertices that drew in-neighbours, and those of them with
#: more eligible in-edges than the fanout (drawn by Floyd's algorithm)
_DRAWN = REGISTRY.counter("sampler.vertices_drawn")
_SUBSAMPLED = REGISTRY.counter("sampler.vertices_subsampled")


@dataclasses.dataclass
class Block:
    """One bipartite sampling layer.  dst nodes are a prefix of src nodes."""

    src_ids: np.ndarray          # (P_src,) shard-local node ids (padded w/ 0)
    n_src: int
    n_dst: int
    edge_src: np.ndarray         # (P_e,) indices into src_ids
    edge_dst: np.ndarray         # (P_e,) indices into [0, n_dst)
    edge_mask: np.ndarray        # (P_e,) bool
    dst_remote_mask: np.ndarray  # (P_dst,) bool — dst rows served from cache
    dst_remote_slot: np.ndarray  # (P_dst,) int32 — row in the remote cache
    dst_mask: np.ndarray         # (P_dst,) bool

    @property
    def p_src(self) -> int:
        return int(self.src_ids.shape[0])

    @property
    def p_dst(self) -> int:
        return int(self.dst_remote_mask.shape[0])


@dataclasses.dataclass
class MiniBatch:
    blocks: list[Block]          # blocks[0] consumes hop-L nodes (h^0 input)
    seeds: np.ndarray            # root training vertices (shard-local ids)
    seed_mask: np.ndarray        # (P_seed,) bool
    input_ids: np.ndarray        # == blocks[0].src_ids (hop-L nodes, all local)
    # remote cache rows touched at each layer l (1..L-1): used by the
    # dynamic-pull runtime (§4.3) and the cost model.
    remote_slots_used: list[np.ndarray]


def _pad_to(x: np.ndarray, n: int, fill=0) -> np.ndarray:
    out = np.full((n,) + x.shape[1:], fill, dtype=x.dtype)
    out[: len(x)] = x
    return out


def _round_up(n: int, m: int = 128) -> int:
    return max(m, ((n + m - 1) // m) * m)


def _local_first_csr(shard: ClientShard):
    """A copy of the shard's in-edge CSR with each row's local sources
    moved ahead of its remote ones (order within each kind kept), and
    each row's count of local sources.  The shard's own arrays are left
    as they are: propagation and evaluation read them in their order."""
    indptr = np.asarray(shard.indptr, np.int64)
    indices = np.asarray(shard.indices)
    deg = np.diff(indptr)
    is_local = indices < shard.num_local
    n_before = np.zeros(len(indices) + 1, np.int64)   # local edges before e
    np.cumsum(is_local, out=n_before[1:])
    n_local = n_before[indptr[1:]] - n_before[indptr[:-1]]
    row = np.repeat(np.arange(len(deg)), deg)
    first = indptr[row]
    local_rank = n_before[:-1] - n_before[first]
    remote_rank = np.arange(len(indices)) - first - local_rank
    dest = first + np.where(is_local, local_rank, n_local[row] + remote_rank)
    nbrs = np.empty(len(indices), indices.dtype)
    nbrs[dest] = indices
    return indptr, nbrs, n_local


class NeighborSampler:
    """Uniform fanout sampler over a :class:`ClientShard`."""

    def __init__(
        self,
        shard: ClientShard,
        fanout: int,
        num_layers: int,
        batch_size: int,
        *,
        seed: int = 0,
    ):
        self.shard = shard
        self.fanout = fanout
        self.L = num_layers
        self.batch_size = batch_size
        self.rng = np.random.default_rng(seed + 7919 * shard.client_id)
        n_total = len(shard.global_ids)
        # Static pads per hop: B*(f+1)^h capped by shard size.
        self._p_nodes = [
            _round_up(min(batch_size * (fanout + 1) ** h, n_total))
            for h in range(num_layers + 1)
        ]
        self._p_edges = [
            _round_up(min(batch_size * (fanout + 1) ** h, n_total) * fanout)
            for h in range(num_layers)
        ]
        self._train = shard.train_vertices()
        self._indptr, self._nbrs, self._n_local_nbrs = _local_first_csr(shard)
        self._deg = np.diff(self._indptr)
        self._pos = np.zeros(n_total, np.int64)   # node id -> block position

    # -- sampling --------------------------------------------------------

    def _floyd(self, d: np.ndarray) -> np.ndarray:
        """A uniform ``fanout``-subset of ``[0, d)`` for each row of ``d``
        (every ``d > fanout``), without replacement: Floyd's algorithm,
        one draw per column over all rows at once."""
        f = self.fanout
        out = np.empty((len(d), f), np.int64)
        for i in range(f):
            j = d - f + i
            t = self.rng.integers(0, j + 1)
            taken = (out[:, :i] == t[:, None]).any(axis=1)
            out[:, i] = np.where(taken, j, t)
        return out

    def _sample_neighbors(self, frontier: np.ndarray, local_only: bool):
        """Sample ≤fanout in-neighbours for each LOCAL node in frontier.

        Returns (edge_src_ids, edge_dst_ids) in shard-local node ids,
        grouped by dst in frontier order.  Remote frontier nodes are
        skipped (rule 2); with ``local_only`` only the row's local
        prefix is eligible."""
        f = self.fanout
        u = frontier[frontier < self.shard.num_local]
        d = (self._n_local_nbrs if local_only else self._deg)[u]
        u, d = u[d > 0], d[d > 0]
        cols = np.tile(np.arange(f, dtype=np.int64), (len(u), 1))
        sub = d > f
        n_sub = int(np.count_nonzero(sub))
        if n_sub:
            cols[sub] = self._floyd(d[sub])
        _DRAWN.inc(len(u))
        _SUBSAMPLED.inc(n_sub)
        take = cols < d[:, None]
        e_src = self._nbrs[(self._indptr[u][:, None] + cols)[take]]
        return e_src.astype(np.int64), np.repeat(u, np.minimum(d, f))

    def sample_batch(self, seeds: np.ndarray) -> MiniBatch:
        sh, L = self.shard, self.L
        layers: list[np.ndarray] = [np.asarray(seeds, dtype=np.int64)]
        layer_edges: list[tuple[np.ndarray, np.ndarray]] = []
        for hop in range(1, L + 1):
            cur = layers[-1]
            e_src, e_dst = self._sample_neighbors(cur, local_only=(hop == L))
            new = np.zeros(len(self._pos), bool)  # sorted e_src not in cur
            new[e_src] = True
            new[cur] = False
            # dst-prefix ordering
            layers.append(np.concatenate([cur, np.flatnonzero(new)]))
            layer_edges.append((e_src, e_dst))

        blocks: list[Block] = []
        remote_used: list[np.ndarray] = []
        pos = self._pos
        # GNN layer l (1-indexed) consumes node set layers[L-l+1], produces
        # layers[L-l]; edges are layer_edges[L-l].
        for l in range(1, L + 1):
            src_nodes = layers[L - l + 1]
            dst_nodes = layers[L - l]
            e_src, e_dst = layer_edges[L - l]
            pos[src_nodes] = np.arange(len(src_nodes))
            es, ed = pos[e_src], pos[e_dst]
            p_src = self._p_nodes[L - l + 1]
            p_dst = self._p_nodes[L - l]
            p_e = self._p_edges[L - l]
            remote = dst_nodes >= sh.num_local
            slot = np.where(remote, dst_nodes - sh.num_local, 0)
            blocks.append(Block(
                src_ids=_pad_to(src_nodes, p_src),
                n_src=len(src_nodes),
                n_dst=len(dst_nodes),
                edge_src=_pad_to(es, p_e),
                edge_dst=_pad_to(ed, p_e),
                edge_mask=_pad_to(np.ones(len(es), bool), p_e, False),
                dst_remote_mask=_pad_to(remote, p_dst, False),
                dst_remote_slot=_pad_to(slot.astype(np.int32), p_dst),
                dst_mask=_pad_to(np.ones(len(dst_nodes), bool), p_dst, False),
            ))
            if l < L:   # layer l output = h^l; remote rows read cache[l]
                remote_used.append(np.unique(slot[remote]).astype(np.int64))

        p_seed = self._p_nodes[0]
        # Rule 3: h^0 (features) are never aggregated for remote vertices —
        # the first block's edge sources must all be local.  (The cumulative
        # src node set MAY contain remote nodes from earlier hops; their
        # feature rows are never read as edge sources and their outputs are
        # overwritten from the embedding cache.)
        b0 = blocks[0]
        src_of_edges = b0.src_ids[b0.edge_src[b0.edge_mask]]
        assert np.all(src_of_edges < sh.num_local)
        return MiniBatch(
            blocks=blocks,
            seeds=_pad_to(layers[0], p_seed),
            seed_mask=_pad_to(np.ones(len(layers[0]), bool), p_seed, False),
            input_ids=blocks[0].src_ids,
            remote_slots_used=remote_used,
        )

    def epoch(self, *, shuffle: bool = True) -> Iterator[MiniBatch]:
        order = self._train.copy()
        if shuffle:
            self.rng.shuffle(order)
        for i in range(0, len(order), self.batch_size):
            yield self.sample_batch(order[i: i + self.batch_size])

    def num_batches(self) -> int:
        return (len(self._train) + self.batch_size - 1) // self.batch_size
