"""Federated GNN training runtime (paper §3) with OptimES strategies (§4).

One process simulates the cross-silo deployment: K client shards train in
(logical) parallel; the aggregation server FedAvg-aggregates; the
remote-embedding exchange subsystem (repro.exchange: wire codec × delta
pushes × transport shards, per Strategy knobs) mediates every pull /
push / prefetch / dynamic-pull against the embedding store.  Compute is
*measured* (wall clock of jitted steps); network is *modelled* by
:class:`NetworkModel` — recorded separately per phase, so every paper
figure can be regenerated.

Numerical faithfulness notes:
  * The embedding server's content is static within a round (clients pull
    previous-round values).  Prefetch (§4.3) therefore changes only the
    *timing*, never the numerics — we fill the client cache at round start
    and account pull time per-strategy.  Pruning and overlap DO change
    numerics and are implemented numerically (smaller expanded subgraph;
    stale epoch-(ε−1) push embeddings).  Lossy wire codecs (fp16/int8)
    and τ>0 delta pushes also change numerics — by design, both
    directions of the wire are honest.  Transport sharding never does
    (row-independent codecs).
  * Round wall time = max over clients (they run in parallel silos)
    + aggregation/validation (~100 ms in the paper; we measure ours).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # break the repro.exchange → repro.core import cycle
    from repro.exchange import ExchangeClient, PushPlan

from repro.fedsvc.aggregation import fedavg_leaves
from repro.graphs.graph import Graph
from repro.graphs.partition import (ClientShard, bfs_partition,
                                    make_client_shards)
from repro.graphs.sampler import NeighborSampler
from repro.models import gnn
from repro.obsv.trace import TRACE, install_jax_hooks
from repro.optim import Optimizer, adam

from .cost_model import NetworkModel
from .pruning import score_remote_nodes, top_fraction
from .strategies import Strategy


@dataclasses.dataclass
class PhaseTimes:
    pull: float = 0.0
    train: float = 0.0
    dynamic_pull: float = 0.0   # §4.3 on-demand pulls (hatched blue stack)
    push_compute: float = 0.0
    push_transfer: float = 0.0
    agg: float = 0.0

    def client_total(self, *, overlap: bool, interference: float,
                     epochs: int) -> float:
        """Wall time for one client's round under the §4.2 timeline."""
        push = self.push_compute + self.push_transfer
        train = self.train + self.dynamic_pull
        if overlap and epochs >= 2:
            last_epoch = train / epochs
            head = train - last_epoch
            return self.pull + head + max(last_epoch * interference, push)
        return self.pull + train + push


@dataclasses.dataclass
class ClientRoundResult:
    """One client's share of a federated round — the unit of work the
    in-process simulator and the fedsvc worker process both execute
    (via :meth:`FederatedGNNTrainer.client_round`)."""
    client_id: int
    params: object                           # locally trained pytree
    phases: PhaseTimes
    rpc_sizes: list[int]                     # dynamic-pull RPC sizes
    push_plan: Optional["PushPlan"]          # priced, not yet applied
    weight: float                            # FedAvg weight (train verts)
    loss: float
    client_time: float                       # modelled §4.2 wall time


@dataclasses.dataclass
class RoundStats:
    round_idx: int
    accuracy: float
    round_time: float
    cum_time: float
    phases: PhaseTimes                       # max over clients per phase
    pull_rpc_sizes: list[int]                # nodes per dynamic-pull RPC
    embeddings_stored: int
    train_loss: float


def time_to_accuracy(stats: list[RoundStats], target: float,
                     *, smooth: int = 5) -> Optional[float]:
    """Cumulative time when the ``smooth``-round moving average accuracy
    first reaches ``target`` (paper §5.2 metric)."""
    accs = [s.accuracy for s in stats]
    for i in range(len(accs)):
        lo = max(0, i - smooth + 1)
        if np.mean(accs[lo: i + 1]) >= target:
            return stats[i].cum_time
    return None


def peak_accuracy(stats: list[RoundStats]) -> float:
    return max(s.accuracy for s in stats) if stats else 0.0


def sampled_eval_vertices(g, max_edges: int, seed: int) -> np.ndarray:
    """Seeded uniform vertex sample whose in-edge mass fits ``max_edges``.

    The unbiased replacement for the old vertex-*prefix* fallback: a
    prefix inherits whatever ordering the store was built with (RMAT
    hubs first, SBM blocks contiguous), so prefix accuracy estimates a
    different population than the full graph.  A uniform permutation
    prefix estimates the same one.  Always returns ≥ 1 vertex, sorted
    ascending."""
    deg = np.diff(np.asarray(g.indptr))
    rng = np.random.default_rng((seed, 104729))
    perm = rng.permutation(g.num_vertices)
    k = int(np.searchsorted(np.cumsum(deg[perm]), max_edges, side="right"))
    return np.sort(perm[: max(1, k)]).astype(np.int64)


def eval_arrays_for(g, sel: np.ndarray) -> dict:
    """``full_propagate`` inputs over the subgraph induced by the sorted
    vertex selection ``sel`` (edges with both endpoints selected, ids
    remapped to positions in ``sel``).  With ``sel == arange(V)`` this
    reproduces the exact full-graph arrays bit-for-bit."""
    indptr = np.asarray(g.indptr)
    starts = indptr[sel]
    counts = (indptr[sel + 1] - starts).astype(np.int64)
    total = int(counts.sum())
    # CSR range-gather: positions of every selected vertex's in-edges
    offsets = np.zeros(len(sel) + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    pos = (np.arange(total, dtype=np.int64)
           - np.repeat(offsets[:-1], counts) + np.repeat(starts, counts))
    e_src = np.asarray(g.indices[pos], dtype=np.int64)
    e_dst = np.repeat(np.arange(len(sel), dtype=np.int64), counts)
    # drop edges whose source is outside the selection, remap the rest
    loc = np.minimum(np.searchsorted(sel, e_src), len(sel) - 1)
    keep = sel[loc] == e_src
    return {
        "edge_src": jnp.asarray(loc[keep], jnp.int32),
        "edge_dst": jnp.asarray(e_dst[keep], jnp.int32),
        "src_is_remote": jnp.zeros(int(keep.sum()), bool),
        "num_local": len(sel),
        "features": jnp.asarray(np.asarray(g.features[sel]), jnp.float32),
    }


class FederatedGNNTrainer:
    def __init__(
        self,
        graph: Graph,
        num_clients: int,
        strategy: Strategy,
        *,
        conv: str = "graphconv",
        num_layers: int = 3,
        hidden: int = 32,
        fanout: int = 5,
        batch_size: int = 64,
        epochs_per_round: int = 3,
        lr: float = 1e-2,
        optimizer: Optimizer | None = None,
        net: NetworkModel | None = None,
        shard_nets: list[NetworkModel] | None = None,
        transport_addrs: list | None = None,
        seed: int = 0,
        part: np.ndarray | None = None,
        shards: list[ClientShard | None] | None = None,
        only_clients: list[int] | None = None,
        eval_max_edges: int = 4_000_000,
        growth=None,
    ):
        self.g = graph
        self.k = num_clients
        self.strategy = strategy
        self.conv = conv
        self.L = num_layers
        self.hidden = hidden
        self.fanout = fanout
        self.batch_size = batch_size
        self.epochs = epochs_per_round
        self.lr = lr
        self.opt = optimizer or adam(lr)
        self.net = net or NetworkModel()
        # heterogeneous per-shard links (ShardedTransport); default: the
        # trainer-wide NetworkModel replicated per shard
        self.shard_nets = shard_nets
        # live embed_server listeners, one per shard (Strategy.transport
        # = "tcp", or inferred when addresses are given)
        self.transport_addrs = transport_addrs
        self.seed = seed
        # shard-local mode (fedsvc workers): build samplers / caches /
        # exchange registrations only for the owned clients; with
        # prebuilt ``shards`` (an mmap store's shard dir) the graph is
        # never re-scanned either.
        self.only_clients = None if only_clients is None \
            else sorted(int(c) for c in only_clients)
        self._prebuilt_shards = shards
        self.eval_max_edges = eval_max_edges
        # dynamic-graph runtime (repro.dyngraph.GrowthRuntime-shaped):
        # apply_growth() advances it between rounds and rebuilds every
        # shard-derived structure when the graph jumps.
        self.growth = growth
        self._growth_round = 0        # round of the last graph jump
        self._growth_accs_base = 0    # pre-jump accuracies to ignore (τ)
        if part is None:
            if getattr(graph, "is_store", False):
                # out-of-core plane: single-pass streaming LDG instead
                # of the O(V)-frontier BFS grow
                from repro.graphstore import ldg_partition
                part = ldg_partition(graph, num_clients, seed=seed)
            else:
                part = bfs_partition(graph, num_clients, seed=seed)
        self.part = part
        install_jax_hooks()
        self._setup()

    # -- setup ----------------------------------------------------------------

    def _client_rng(self, ci: int, salt: int) -> np.random.Generator:
        """Per-(client, purpose) generator for the R25-style random
        subset draws: seeded independently of build order, so a
        shard-local worker (only_clients=...) draws the same subsets as
        the full in-process trainer."""
        return np.random.default_rng((self.seed, salt, ci))

    def _build_shards(self, limit, retained_remote=None
                      ) -> list[ClientShard]:
        """Shard extraction, dispatched per graph plane: streaming over
        an mmap store, materialized for an in-memory Graph — outputs are
        bit-identical (gated in tests/test_graphstore.py)."""
        from repro.graphstore import build_client_shards
        return build_client_shards(
            self.g, self.part, retention_limit=limit,
            retained_remote=retained_remote, seed=self.seed)

    def _setup(self) -> None:
        st = self.strategy
        self.owned = list(range(self.k)) if self.only_clients is None \
            else self.only_clients
        self._registered = np.zeros(0, np.int64)  # gids exchange knows
        self._build_shard_state()
        shards = self.shards

        # remote-embedding exchange: transport (embedding server shard(s)
        # behind modelled links) + one codec/delta-aware client per silo
        from repro.exchange import ExchangeClient, make_transport
        if st.shard_placement not in ("hash", "pull_frequency"):
            raise ValueError(
                f"unknown shard_placement {st.shard_placement!r}; "
                "expected hash | pull_frequency")
        if st.use_embeddings:
            self.exchange = make_transport(
                self.L, self.hidden, kind=st.transport,
                num_shards=st.num_server_shards,
                nets=self.shard_nets if self.shard_nets is not None
                else self.net,
                addrs=self.transport_addrs, codec=st.codec)
            if st.shard_placement == "pull_frequency":
                if not hasattr(self.exchange, "rebalance_by_pulls"):
                    raise ValueError(
                        "shard_placement='pull_frequency' needs the "
                        "sharded in-process transport (num_server_shards "
                        "> 1, transport != 'tcp'): "
                        f"{type(self.exchange).__name__} cannot migrate "
                        "rows")
                self.exchange.track_pulls = True
            self.ex_clients: list[ExchangeClient | None] = [
                None if shards[ci] is None else
                ExchangeClient(self.exchange, st.codec,
                               delta_threshold=st.delta_threshold,
                               error_feedback=st.error_feedback)
                for ci in range(self.k)
            ]
        else:
            self.exchange = None
            self.ex_clients = [None] * self.k
        self._register_shard_nodes()
        self._build_client_state()
        self._build_eval_state()

        # model + jitted train step
        self.params = gnn.init_gnn(jax.random.PRNGKey(self.seed), self.conv,
                                   self.g.feat_dim, self.hidden,
                                   self.g.num_classes, self.L)
        opt = self.opt

        def _step(params, opt_state, batch, features, caches, labels):
            loss, grads = jax.value_and_grad(
                functools.partial(gnn.loss_fn, conv=self.conv))(
                    params, batch, features, caches, labels)
            params, opt_state = opt.step(params, grads, opt_state)
            return params, opt_state, loss

        self._train_step = jax.jit(_step)
        self._treedef = jax.tree_util.tree_structure(self.params)
        self.acc_history: list[float] = []   # finished-round accuracies

    def _build_shard_state(self) -> None:
        """Everything derived from (graph, part): shards, reciprocal
        push sets, push-row indices, prefetch sets.  Re-run after each
        graph growth jump."""
        st = self.strategy
        limit = 0 if not st.use_embeddings else st.retention_limit
        if self._prebuilt_shards is not None:
            # prebuilt (mmap'd) shards: a worker never re-scans the
            # graph.  Score-based pruning still applies, shard-locally.
            shards = list(self._prebuilt_shards)
            if st.use_embeddings and st.scored_prune_frac is not None:
                from repro.graphs.partition import filter_shard_remote
                for ci in self.owned:
                    sh = shards[ci]
                    scores = score_remote_nodes(sh, st.score_kind, self.L)
                    keep = top_fraction(scores, st.scored_prune_frac,
                                        rng=self._client_rng(ci, 1),
                                        random_subset=st.random_subset)
                    shards[ci] = filter_shard_remote(
                        sh, sh.pull_nodes[keep])
        else:
            # NOTE: without prebuilt shards every client's shard is
            # extracted (the reciprocal push recompute below needs all
            # pull sets), so this fallback holds O(E) shard edges even
            # under only_clients — bake shards with launch/build_store
            # for stores where that matters.
            shards = self._build_shards(limit)

            # score-based pruning (§4.1.2): keep top-f% pull nodes per
            # client, scored on the (retention-pruned) expanded subgraph.
            # Same seed ⇒ the same retention edges survive before the set
            # filter applies.
            if st.use_embeddings and st.scored_prune_frac is not None:
                retained2 = {}
                for sh in shards:
                    scores = score_remote_nodes(sh, st.score_kind, self.L)
                    keep = top_fraction(scores, st.scored_prune_frac,
                                        rng=self._client_rng(sh.client_id, 1),
                                        random_subset=st.random_subset)
                    retained2[sh.client_id] = sh.pull_nodes[keep]
                shards = self._build_shards(limit, retained_remote=retained2)
        self.shards = shards

        # push sets follow the *retained* pull sets: client k pushes exactly
        # the nodes other clients retained (pruning shrinks pushes, §4.1.1).
        # Possible only when every shard is visible; a shard-local worker
        # keeps the reciprocal sets stored at shard-build time (a superset
        # under scored pruning — extra pushed rows are simply never read).
        part = self.part
        if all(sh is not None for sh in shards):
            for sh in shards:
                wanted = [
                    other.pull_nodes[part[other.pull_nodes] == sh.client_id]
                    for other in shards if other.client_id != sh.client_id]
                sh.push_nodes = np.unique(np.concatenate(wanted)) \
                    if wanted else np.zeros(0, np.int64)

        # push-node local-row indices, hoisted: both push paths
        # (pretrain_round, _compute_push) used to rebuild the
        # global→local dict per client per round, O(num_local) each time.
        self.push_rows: list[np.ndarray | None] = [None] * self.k
        for ci in self.owned:
            sh = shards[ci]
            g2l = {int(g): i
                   for i, g in enumerate(sh.global_ids[:sh.num_local])}
            self.push_rows[ci] = \
                np.fromiter((g2l[int(g)] for g in sh.push_nodes),
                            np.int64, len(sh.push_nodes))

        # prefetch scores (§4.3) on the final expanded shard
        self.prefetch_sets: list[np.ndarray | None] = [None] * self.k
        for ci in self.owned:
            sh = shards[ci]
            if st.use_embeddings and st.prefetch_frac is not None:
                scores = score_remote_nodes(sh, st.score_kind, self.L)
                idx = top_fraction(scores, st.prefetch_frac,
                                   rng=self._client_rng(ci, 2),
                                   random_subset=st.random_subset)
            else:
                idx = np.arange(len(sh.pull_nodes))
            self.prefetch_sets[ci] = idx

    def _register_shard_nodes(self) -> None:
        """Register the owned shards' pull/push sets with the exchange.

        Registration is idempotent server-side (the capacity-doubling
        table keeps existing rows), so after a growth jump only the
        genuinely new boundary vertices matter — those are counted into
        the growth runtime's boundary-registration metric."""
        if self.exchange is None:
            return
        fresh = 0
        for ci in self.owned:
            sh = self.shards[ci]
            for gids in (sh.pull_nodes, sh.push_nodes):
                if self.growth is not None and len(gids):
                    fresh += len(np.setdiff1d(gids, self._registered))
                    self._registered = np.union1d(self._registered, gids)
                self.exchange.register(gids)
        if self.growth is not None and fresh:
            self.growth.record_boundary(fresh)

    def _build_client_state(self) -> None:
        """Per-client training state over the current shards: samplers,
        device arrays, embedding caches."""
        shards = self.shards
        self.samplers: list[NeighborSampler | None] = [None] * self.k
        self.shard_arrays: list[dict | None] = [None] * self.k
        self.feats = [None] * self.k
        self.labels = [None] * self.k
        for ci in self.owned:
            sh = shards[ci]
            self.samplers[ci] = NeighborSampler(
                sh, self.fanout, self.L, self.batch_size, seed=self.seed)
            self.shard_arrays[ci] = gnn.shard_to_arrays(sh)
            self.feats[ci] = jnp.asarray(sh.features, jnp.float32)
            self.labels[ci] = jnp.asarray(sh.labels, jnp.int32)
        self._caches: list[list[jnp.ndarray] | None] = [
            None if sh is None else
            [jnp.zeros((max(1, sh.num_remote), self.hidden), jnp.float32)
             for _ in range(self.L - 1)]
            for sh in shards
        ]

    def _build_eval_state(self) -> None:
        # global eval graph (aggregation server's held-out test set):
        # full-neighbourhood forward over the whole graph — or, past
        # ``eval_max_edges``, over a seeded uniform vertex sample whose
        # induced edges fit the budget (the unbiased estimator for
        # million-vertex stores; the old vertex-prefix fallback skewed
        # toward whatever the store's build order put first).
        # Shard-local workers never evaluate and skip the arrays.
        if self.only_clients is None:
            if self.g.num_edges > self.eval_max_edges:
                sel = sampled_eval_vertices(self.g, self.eval_max_edges,
                                            self.seed)
            else:
                sel = np.arange(self.g.num_vertices, dtype=np.int64)
            self.eval_gids = sel
            self.eval_arrays = eval_arrays_for(self.g, sel)
            self.test_idx = np.nonzero(
                ~np.asarray(self.g.train_mask[sel]))[0]
        else:
            self.eval_gids = None
            self.eval_arrays = None
            self.test_idx = None

    # -- dynamic graphs (repro.dyngraph) ---------------------------------------

    def apply_growth(self, epoch: int,
                     round_idx: int | None = None) -> bool:
        """Advance the growth runtime to ``epoch`` and, if the graph
        jumped, swap in the merged view and rebuild every shard-derived
        structure (shards, push sets, samplers, caches, eval sample).
        Model params and the exchange survive — only the *new* boundary
        vertices are registered (the server's capacity-doubling path).
        ``round_idx`` stamps the jump so the plateau-τ schedule restarts
        from it.  → True when anything changed."""
        if self.growth is None:
            return False
        if not self.growth.advance_to(epoch, part=self.part):
            return False
        self.g = self.growth.graph
        self.part = self.growth.part
        if round_idx is not None:
            self._growth_round = int(round_idx)
            self._growth_accs_base = int(round_idx)
        self._refresh_after_growth()
        return True

    def _refresh_after_growth(self) -> None:
        self._prebuilt_shards = None    # extracted pre-growth: stale
        self._build_shard_state()
        self._register_shard_nodes()
        self._build_client_state()
        self._build_eval_state()

    # -- params <-> leaves (fedsvc control plane) ------------------------------

    def params_leaves(self, params=None) -> list[np.ndarray]:
        """Flat numpy leaves of ``params`` (default: the global model),
        in canonical tree_flatten order — the coordinator wire format."""
        return [np.asarray(l) for l in
                jax.tree_util.tree_leaves(
                    self.params if params is None else params)]

    def leaves_to_params(self, leaves):
        """Inverse of :meth:`params_leaves`."""
        return jax.tree_util.tree_unflatten(
            self._treedef, [jnp.asarray(l) for l in leaves])

    def set_round_tau(self, round_idx: int, accuracies=None) -> None:
        """Apply the adaptive-τ schedule (Strategy.delta_schedule) for
        this round to every client's delta tracker.  After a graph
        growth jump the schedule restarts from the jump round: linear
        warm-up re-ramps, and the plateau detector only sees post-jump
        accuracies (pre-jump plateaus don't count against a graph the
        model has never trained on)."""
        tau = self.strategy.delta_for_round(
            round_idx - self._growth_round,
            list(self.acc_history if accuracies is None
                 else accuracies)[self._growth_accs_base:])
        if tau is None:
            return
        for ex in self.ex_clients:
            if ex is not None and ex.delta is not None:
                ex.delta.tau = tau

    # -- embedding exchange helpers ---------------------------------------------

    @property
    def server(self):
        """Back-compat alias: the embedding-server side of the exchange
        (a Transport; exposes num_embeddings_stored / log / memory_bytes)."""
        return self.exchange

    def _fill_cache(self, ci: int) -> None:
        """Materialise this round's pull-node embeddings into the client
        cache (numerics; timing handled separately).  Values go through
        the wire codec, so lossy codecs shape training numerics here."""
        sh = self.shards[ci]
        if self.exchange is None or len(sh.pull_nodes) == 0:
            return
        with TRACE.span("client.pull", args={"client": ci,
                                             "rows": len(sh.pull_nodes)}):
            vals = self.ex_clients[ci].peek(sh.pull_nodes)
            pad = max(1, sh.num_remote) - sh.num_remote
            self._caches[ci] = [
                jnp.asarray(np.concatenate([
                    vals[l], np.zeros((pad, self.hidden), np.float32)]))
                if sh.num_remote else self._caches[ci][l]
                for l in range(self.L - 1)
            ]

    def _pull_time(self, ci: int, minibatches) -> tuple[float, float, list[int]]:
        """(upfront pull s, dynamic pull s, nodes-per-dynamic-RPC sizes)."""
        sh = self.shards[ci]
        st = self.strategy
        ex = self.ex_clients[ci]
        if self.exchange is None or len(sh.pull_nodes) == 0:
            return 0.0, 0.0, []
        if st.prefetch_frac is None:
            return ex.pull_cost(sh.pull_nodes), 0.0, []
        # §4.3: batched prefetch of top-x% + per-minibatch on-demand RPCs.
        pre = self.prefetch_sets[ci]
        t_pre = ex.pull_cost(sh.pull_nodes[pre])
        present = [np.zeros(sh.num_remote, bool) for _ in range(self.L - 1)]
        for p in present:
            p[pre] = True
        t_dyn, sizes = 0.0, []
        for mb in minibatches:
            miss_gids = []
            for l, used in enumerate(mb.remote_slots_used):
                miss = used[~present[l][used]]
                if len(miss):
                    # remote slot i ↔ sh.pull_nodes[i] (shard layout:
                    # global_ids = [local, pull_nodes])
                    miss_gids.append(sh.pull_nodes[miss])
                present[l][miss] = True
            if miss_gids:
                gids = np.concatenate(miss_gids)
                t_dyn += ex.dynamic_pull(gids)
                sizes.append(len(gids))
        return t_pre, t_dyn, sizes

    def _compute_push(self, ci: int, params) -> tuple[Optional[PushPlan],
                                                      float, float]:
        """Forward pass for push-node embeddings (§3.2.2 push phase).
        Returns (delta-filtered+encoded push plan, compute s, transfer s)."""
        sh = self.shards[ci]
        if self.exchange is None or len(sh.push_nodes) == 0:
            return None, 0.0, 0.0
        with TRACE.span("client.push_compute", args={"client": ci}):
            t0 = time.perf_counter()
            outs = gnn.full_propagate(params, self.shard_arrays[ci],
                                      self._caches[ci], conv=self.conv)
            jax.block_until_ready(outs)
            t_compute = time.perf_counter() - t0
            rows = self.push_rows[ci]
            vals = [np.asarray(outs[l])[rows] for l in range(self.L - 1)]
            plan = self.ex_clients[ci].plan_push(sh.push_nodes, vals)
        return plan, t_compute, plan.transfer_time

    # -- lifecycle ---------------------------------------------------------------

    def pretrain_round(self, client_ids: list[int] | None = None) -> None:
        """§3.2.1: initialise push-node embeddings on the unexpanded local
        subgraphs (remote neighbours masked) and seed the server.  A
        fedsvc worker passes its own ``client_ids`` so each process
        seeds exactly the rows it owns (push sets are disjoint across
        clients, so order never matters)."""
        if self.exchange is None:
            return
        for ci in (self.owned if client_ids is None else client_ids):
            sh = self.shards[ci]
            if len(sh.push_nodes) == 0:
                continue
            outs = gnn.full_propagate(self.params, self.shard_arrays[ci],
                                      None, conv=self.conv)
            rows = self.push_rows[ci]
            vals = [np.asarray(outs[l])[rows] for l in range(self.L - 1)]
            self.ex_clients[ci].push(sh.push_nodes, vals)

    def export_for_serving(self) -> dict:
        """Publish the trained state for the serving plane (gnnserve).

        Training only ever stores the reciprocal push-node rows; a
        query can land on *any* vertex, so this registers every owned
        shard's local vertices with the exchange and pushes their full
        h^1..h^{L-1} (full-neighbourhood propagate against the current
        caches).  Rows cross the wire through a plain
        :class:`ExchangeClient` — the codec applies and row versions
        bump, but delta shadows / error-feedback residuals are left
        untouched (serving must not perturb a resumable trainer).

        Returns the bundle ``gnnserve.engine.build_serving`` consumes.
        """
        if self.exchange is None:
            raise RuntimeError("export_for_serving needs an embedding-"
                               "sharing strategy (use_embeddings=True)")
        from repro.exchange import ExchangeClient
        pub = ExchangeClient(self.exchange, self.strategy.codec)
        for ci in self.owned:
            sh = self.shards[ci]
            self._fill_cache(ci)
            outs = gnn.full_propagate(self.params, self.shard_arrays[ci],
                                      self._caches[ci], conv=self.conv)
            gids = np.asarray(sh.global_ids[:sh.num_local], np.int64)
            pub.register(gids)
            pub.push(gids, [np.asarray(outs[l])
                            for l in range(self.L - 1)])
        return {
            "params": self.params,
            "conv": self.conv,
            "num_layers": self.L,
            "hidden": self.hidden,
            "part": np.asarray(self.part),
            "shards": {ci: self.shards[ci] for ci in self.owned},
            "transport": self.exchange,
            "codec": self.strategy.codec,
        }

    def evaluate(self, params=None) -> float:
        if self.eval_arrays is None:
            raise RuntimeError(
                "shard-local trainer (only_clients=...) has no eval "
                "graph; evaluation belongs to the coordinator")
        outs = gnn.full_propagate(
            self.params if params is None else params,
            self.eval_arrays, None, conv=self.conv)
        pred = np.asarray(jnp.argmax(outs[-1], axis=-1))
        truth = np.asarray(self.g.labels[self.eval_gids[self.test_idx]])
        return float((pred[self.test_idx] == truth).mean())

    def client_round(self, ci: int, params=None, *,
                     fill_cache: bool = True) -> ClientRoundResult:
        """One client's share of a round: cache fill (pull), sampling,
        local epochs, push planning.  The in-process :meth:`run_round`
        loops this over all clients; a fedsvc worker process runs it for
        the client(s) it owns.  The returned push plan is *not* applied
        — the caller commits it once every client has pulled (server
        static within the round, §4.2)."""
        st = self.strategy
        sh = self.shards[ci]
        p = PhaseTimes()
        if fill_cache:
            self._fill_cache(ci)
        # pre-sample the round's minibatches (sampling is part of the
        # measured train phase, like DGL's dataloader)
        t0 = time.perf_counter()
        with TRACE.span("client.sample", args={"client": ci}):
            epochs_batches = [list(self.samplers[ci].epoch())
                              for _ in range(self.epochs)]
        sample_t = time.perf_counter() - t0
        p.pull, p.dynamic_pull, sizes = self._pull_time(
            ci, [mb for ep in epochs_batches for mb in ep])

        params = self.params if params is None else params
        opt_state = self.opt.init(params)
        t_train = sample_t
        push_plan: Optional[PushPlan] = None
        loss = jnp.zeros(())
        for e, batches in enumerate(epochs_batches, start=1):
            t0 = time.perf_counter()
            with TRACE.span("client.train_epoch",
                            args={"client": ci, "epoch": e}):
                with TRACE.span("client.step_inputs"):
                    steps = gnn.epoch_to_arrays(batches)
                for batch in steps:
                    with TRACE.span("client.step_dispatch"):
                        params, opt_state, loss = self._train_step(
                            params, opt_state, batch, self.feats[ci],
                            self._caches[ci], self.labels[ci])
                jax.block_until_ready(loss)
            t_train += time.perf_counter() - t0
            if st.overlap_push and e == self.epochs - 1:
                # §4.2: stale push computed from the epoch-(ε−1) model
                push_plan, p.push_compute, p.push_transfer = \
                    self._compute_push(ci, params)
        if not st.overlap_push or self.epochs < 2:
            push_plan, p.push_compute, p.push_transfer = \
                self._compute_push(ci, params)
        p.train = t_train
        return ClientRoundResult(
            client_id=ci, params=params, phases=p, rpc_sizes=sizes,
            push_plan=push_plan,
            weight=float(len(sh.train_vertices())),
            loss=float(loss),
            client_time=p.client_total(
                overlap=st.overlap_push,
                interference=st.overlap_interference, epochs=self.epochs))

    def run_round(self, round_idx: int, cum_time: float) -> RoundStats:
        assert self.only_clients is None, \
            "run_round needs every client; shard-local trainers drive " \
            "client_round through the fedsvc control plane"
        TRACE.set_context(round=round_idx)
        self.set_round_tau(round_idx)
        # pull-frequency shard rebalancing (ROADMAP): after the first
        # round's pulls are logged, re-place hot rows across the
        # embedding-server shards by observed pull counts (LPT) —
        # numerics are untouched (row-independent codecs), only the
        # per-shard time/byte ledgers move.
        st = self.strategy
        if st.use_embeddings and st.shard_placement == "pull_frequency" \
                and round_idx == st.rebalance_round:
            self.exchange.rebalance_by_pulls()
        phases = PhaseTimes()
        all_rpc_sizes: list[int] = []

        results = [self.client_round(ci) for ci in range(self.k)]
        for res in results:
            all_rpc_sizes += res.rpc_sizes
            for name in ("pull", "train", "dynamic_pull", "push_compute",
                         "push_transfer"):
                setattr(phases, name, max(getattr(phases, name),
                                          getattr(res.phases, name)))

        # all clients pulled before anyone pushes (server is static
        # within the round) — apply the planned pushes now.
        with TRACE.span("round.apply_push"):
            for res in results:
                if res.push_plan is not None:
                    self.ex_clients[res.client_id].apply_push(res.push_plan)

        # FedAvg + validation on the aggregation server.  The leaf-wise
        # fedavg_leaves is shared with the fedsvc coordinator, so the
        # multi-process sync path aggregates with the same float32
        # arithmetic in the same client order.
        t0 = time.perf_counter()
        with TRACE.span("round.aggregate", args={"round": round_idx}):
            weights = [res.weight for res in results]
            agg = fedavg_leaves([self.params_leaves(res.params)
                                 for res in results], weights)
            self.params = self.leaves_to_params(agg)
            acc = self.evaluate()
        t_agg = time.perf_counter() - t0 \
            + 2 * self.net.model_transfer_time(self._num_params())
        phases.agg = t_agg
        self.acc_history.append(acc)
        losses = [res.loss for res in results]

        round_time = max(res.client_time for res in results) + t_agg
        return RoundStats(
            round_idx=round_idx,
            accuracy=acc,
            round_time=round_time,
            cum_time=cum_time + round_time,
            phases=phases,
            pull_rpc_sizes=all_rpc_sizes,
            embeddings_stored=0 if self.exchange is None
            else self.exchange.num_embeddings_stored,
            train_loss=float(np.mean(losses)),
        )

    def train(self, num_rounds: int, *, verbose: bool = False
              ) -> list[RoundStats]:
        self.pretrain_round()
        stats: list[RoundStats] = []
        cum = 0.0
        for r in range(num_rounds):
            if self.growth is not None:
                self.apply_growth(self.growth.epoch_for_round(r), r)
            s = self.run_round(r, cum)
            cum = s.cum_time
            stats.append(s)
            if verbose:
                print(f"  round {r:3d} acc={s.accuracy:.4f} "
                      f"loss={s.train_loss:.3f} t={s.round_time:.3f}s "
                      f"(pull {s.phases.pull:.3f} train {s.phases.train:.3f} "
                      f"dyn {s.phases.dynamic_pull:.3f} "
                      f"push {s.phases.push_compute + s.phases.push_transfer:.3f})")
        return stats

    def _num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))
