"""Graph substrate: construction, partitioning, sampling invariants."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graphs import (NeighborSampler, bfs_partition, edge_cut,
                          from_edges, hash_partition, make_client_shards,
                          make_graph)


def test_from_edges_symmetric_dedup():
    g = from_edges(4, np.array([0, 0, 1, 2, 2]), np.array([1, 1, 2, 3, 0]))
    g.validate()
    # symmetric: every edge has its reverse
    for u in range(4):
        for v in g.neighbours(u):
            assert u in g.neighbours(int(v))
    # dedup: 0-1 appears once per direction
    assert list(g.neighbours(1)).count(0) == 1


def test_presets_statistics():
    g = make_graph("reddit", scale=0.2, seed=0)
    a = make_graph("arxiv", scale=0.2, seed=0)
    assert g.avg_degree() > 3 * a.avg_degree()  # density ordering of Table 1
    assert g.num_classes == 41 and a.num_classes == 40
    assert g.train_mask.mean() > a.train_mask.mean() * 0.8


def _bfs_partition_reference(g, k, seed):
    """Per-vertex Python mirror of the vectorized bfs_partition: same
    level-synchronous growth, water-filled leftovers, and frozen-
    snapshot ranked-admission refinement — the fixed-seed parity oracle
    for the CSR-sliced rewrite."""
    rng = np.random.default_rng(seed)
    n = g.num_vertices
    target = (n + k - 1) // k
    part = np.full(n, -1, dtype=np.int32)
    sizes = np.zeros(k, dtype=np.int64)
    order = rng.permutation(n)
    cursor = 0
    for p in range(k):
        while cursor < n and part[order[cursor]] >= 0:
            cursor += 1
        if cursor >= n:
            break
        frontier = [int(order[cursor])]
        while frontier and sizes[p] < target:
            room = int(target - sizes[p])
            take, rest = frontier[:room], frontier[room:]
            for u in take:
                part[u] = p
            sizes[p] += len(take)
            if rest or sizes[p] >= target:
                break
            nxt = sorted({int(v) for u in take for v in g.neighbours(u)})
            frontier = [v for v in nxt if part[v] < 0]
    # leftovers: sequential-argmin fill counts, handed out to parts in
    # initial-size order, leftover vertices in id order
    left = np.nonzero(part < 0)[0]
    if len(left):
        fills = np.zeros(k, dtype=np.int64)
        s = sizes.copy()
        for _ in range(len(left)):
            p = int(np.argmin(s))
            fills[p] += 1
            s[p] += 1
        recv = np.argsort(sizes, kind="stable")
        seq = [p for p in recv for _ in range(fills[p])]
        for u, p in zip(left, seq):
            part[u] = p
        sizes += fills
    # frozen-snapshot refinement with ranked admission
    lo, hi = int(0.9 * target), int(1.1 * target) + 1
    cnt = np.zeros((n, k), dtype=np.int64)
    for u in range(n):
        for v in g.neighbours(u):
            cnt[u, part[v]] += 1
    best = np.argmax(cnt, axis=1)
    prio = np.empty(n, dtype=np.int64)
    prio[rng.permutation(n)] = np.arange(n)
    cand = [u for u in range(n)
            if len(g.neighbours(u)) and best[u] != part[u]
            and cnt[u, best[u]] > cnt[u, part[u]]
            and sizes[best[u]] < hi and sizes[part[u]] > lo]
    cand.sort(key=lambda u: prio[u])
    seen_dst = np.zeros(k, dtype=np.int64)
    seen_src = np.zeros(k, dtype=np.int64)
    moves = []
    for u in cand:
        d, s_ = int(best[u]), int(part[u])
        if seen_dst[d] < hi - sizes[d] and seen_src[s_] < sizes[s_] - lo:
            moves.append((u, d))
        seen_dst[d] += 1
        seen_src[s_] += 1
    for u, d in moves:
        part[u] = d
    return part


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 9), st.integers(0, 100), st.integers(0, 10_000))
def test_water_fill_matches_sequential_argmin(k, m, seed):
    """_water_fill's claimed semantics: exactly m sequential
    argmin(sizes) assignments (ties → lowest part index)."""
    from repro.graphs.partition import _water_fill
    rng = np.random.default_rng(seed)
    sizes = rng.integers(0, 30, size=k).astype(np.int64)
    got = _water_fill(sizes.copy(), m)
    f = np.zeros(k, np.int64)
    s = sizes.copy()
    for _ in range(m):
        p = int(np.argmin(s))
        f[p] += 1
        s[p] += 1
    np.testing.assert_array_equal(got, f)


@pytest.mark.parametrize("k,seed", [(2, 0), (4, 0), (3, 5)])
def test_bfs_partition_matches_reference(small_graph, k, seed):
    """The vectorized bfs_partition is output-identical to the
    per-vertex reference for fixed seeds (ISSUE-5 satellite gate)."""
    got = bfs_partition(small_graph, k, seed=seed)
    want = _bfs_partition_reference(small_graph, k, seed)
    np.testing.assert_array_equal(got, want)


def test_bfs_partition_balanced_and_better_than_hash(small_graph):
    g = small_graph
    for k in (2, 4):
        part = bfs_partition(g, k, seed=0)
        sizes = np.bincount(part, minlength=k)
        assert sizes.min() >= 0.7 * g.num_vertices / k
        assert edge_cut(g, part) <= edge_cut(g, hash_partition(g, k, seed=0))


def test_client_shards_partition_vertices(small_graph, small_shards):
    shards, part = small_shards
    locals_ = np.concatenate([s.global_ids[: s.num_local] for s in shards])
    assert len(locals_) == small_graph.num_vertices
    assert len(np.unique(locals_)) == small_graph.num_vertices
    for s in shards:
        # pull nodes live on other clients
        assert np.all(part[s.pull_nodes] != s.client_id)
        # push nodes are local
        assert np.all(part[s.push_nodes] == s.client_id)
        # remote rows have no in-edges (structural termination rule)
        assert s.indptr.shape[0] == s.num_local + 1


def test_push_pull_reciprocity(small_shards):
    shards, part = small_shards
    all_pull = np.unique(np.concatenate([s.pull_nodes for s in shards]))
    all_push = np.unique(np.concatenate([s.push_nodes for s in shards]))
    assert np.array_equal(all_pull, all_push)


@pytest.mark.parametrize("fanout,L", [(3, 2), (5, 3)])
def test_sampler_rules(small_shards, fanout, L):
    shards, _ = small_shards
    sh = shards[0]
    s = NeighborSampler(sh, fanout, L, batch_size=16, seed=1)
    for mb in list(s.epoch())[:3]:
        # roots are local training vertices
        seeds = mb.seeds[mb.seed_mask]
        assert np.all(seeds < sh.num_local)
        assert np.all(sh.train_mask[seeds])
        # rule 3: layer-1 block aggregates only local features
        b0 = mb.blocks[0]
        src = b0.src_ids[b0.edge_src[b0.edge_mask]]
        assert np.all(src < sh.num_local)
        # dst-prefix chaining: block l dst pad == block l+1 src pad
        for a, b in zip(mb.blocks, mb.blocks[1:]):
            assert a.p_src == 0 or True
            assert a.n_src >= a.n_dst
        for l in range(L - 1):
            assert mb.blocks[l].n_src == mb.blocks[l + 1 - 1].n_src  # sanity
            assert mb.blocks[l].p_dst == mb.blocks[l + 1].p_src
            assert mb.blocks[l].n_dst == mb.blocks[l + 1].n_src


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(2, 3), st.integers(0, 10_000))
def test_sampler_fanout_bound_property(fanout, L, seed):
    g = make_graph("arxiv", scale=0.05, seed=seed % 17)
    part = bfs_partition(g, 2, seed=seed % 5)
    sh = make_client_shards(g, part)[0]
    s = NeighborSampler(sh, fanout, L, batch_size=8, seed=seed)
    train = sh.train_vertices()
    if len(train) == 0:
        return
    mb = s.sample_batch(train[:8])
    for blk in mb.blocks:
        # each dst node aggregates at most `fanout` sampled neighbours
        dst = blk.edge_dst[blk.edge_mask]
        if len(dst):
            assert np.bincount(dst).max() <= fanout
        # remote dst rows carry valid cache slots
        slots = blk.dst_remote_slot[blk.dst_remote_mask]
        assert np.all(slots < max(1, sh.num_remote))


# -- vectorised draw: uniformity, whole rows, hop L, shard untouched ----------

def _drawn(mb, block=-1):
    """(src, dst) shard-local ids of one block's sampled edges."""
    b = mb.blocks[block]
    m = b.edge_mask
    return b.src_ids[b.edge_src[m]], b.src_ids[b.edge_dst[m]]


def _eligible(sh, u, local_only):
    nbrs = sh.indices[sh.indptr[u]: sh.indptr[u + 1]]
    return nbrs[nbrs < sh.num_local] if local_only else nbrs


def _interleaved_shard():
    """Five local vertices (ids 5-8 remote): vertex 0 reads
    [5, 1, 6, 2, 7, 3, 8], remote and local ids interleaved; vertices 1
    and 2 read [4] and [0]; 3 and 4 read nothing."""
    from repro.graphs.partition import ClientShard
    indices = np.array([5, 1, 6, 2, 7, 3, 8, 4, 0], np.int32)
    return ClientShard(
        client_id=0, indptr=np.array([0, 7, 8, 9, 9, 9]), indices=indices,
        global_ids=np.arange(9), num_local=5,
        features=np.zeros((5, 2), np.float32), labels=np.zeros(5, np.int64),
        train_mask=np.ones(5, bool), pull_nodes=np.arange(5, 9),
        push_nodes=np.zeros(0, np.int64), all_pull_nodes=np.arange(5, 9))


@pytest.mark.parametrize("fanout", [2, 5])
@pytest.mark.parametrize("local_only", [False, True])
def test_subsampled_edges_drawn_uniformly(small_shards, fanout, local_only):
    """A vertex with more eligible in-edges than the fanout draws each
    with frequency fanout/d: chi-square over fixed-seed batches."""
    sh = small_shards[0][0]
    # L=1 makes hop 1 the hop-L (local-only) draw; L=2 draws all in-edges
    s = NeighborSampler(sh, fanout, 1 if local_only else 2, batch_size=1,
                        seed=3)
    d_all = [len(_eligible(sh, u, local_only)) for u in range(sh.num_local)]
    u = int(np.argmax(d_all))
    elig = _eligible(sh, u, local_only)
    d = len(elig)
    assert d > fanout
    n = 3000
    counts = dict.fromkeys(elig.tolist(), 0)
    for _ in range(n):
        src, dst = _drawn(s.sample_batch(np.array([u])))
        assert np.all(dst == u) and len(src) == fanout
        assert len(np.unique(src)) == fanout
        for v in src.tolist():
            counts[v] += 1        # KeyError: an ineligible source
    obs = np.array(list(counts.values()), float)
    exp = n * fanout / d
    chi2 = float(((obs - exp) ** 2 / exp).sum())
    # E[chi2] = d - fanout without replacement; allow a wide margin
    assert chi2 < (d - 1) + 6 * np.sqrt(2 * (d - 1)), (chi2, d)
    assert obs.min() > 0


@pytest.mark.parametrize("local_only", [False, True])
def test_short_rows_taken_whole(small_shards, local_only):
    """With d ≤ fanout every eligible in-edge is taken, each once."""
    fanout = 5
    for sh in small_shards[0]:
        d = np.array([len(_eligible(sh, u, local_only))
                      for u in range(sh.num_local)])
        short = np.nonzero((d > 0) & (d <= fanout))[0]
        assert len(short)
        s = NeighborSampler(sh, fanout, 1 if local_only else 2,
                            batch_size=len(short), seed=5)
        src, dst = _drawn(s.sample_batch(short))
        for u in short:
            got = np.sort(src[dst == u])
            assert np.array_equal(got, np.sort(_eligible(sh, u, local_only)))


@pytest.mark.parametrize("fanout", [1, 2, 3, 5])
@pytest.mark.parametrize("which", ["built", "interleaved"])
def test_hop_l_draws_local_sources_only(small_shards, fanout, which):
    """Hop L (here the only hop) draws min(fanout, local in-degree) local
    sources from rows that interleave local and remote ids."""
    sh = small_shards[0][0] if which == "built" else _interleaved_shard()
    rows = [sh.indices[sh.indptr[u]: sh.indptr[u + 1]] >= sh.num_local
            for u in range(sh.num_local)]
    # precondition: some row reads a remote id before a local one
    assert any(np.any(r[:-1] & ~r[1:]) for r in rows)
    s = NeighborSampler(sh, fanout, 1, batch_size=sh.num_local, seed=11)
    for _ in range(5):
        src, dst = _drawn(s.sample_batch(np.arange(sh.num_local)))
        assert np.all(src < sh.num_local)
        n_local = np.array([np.count_nonzero(~r) for r in rows])
        per_dst = np.bincount(dst, minlength=sh.num_local)
        assert np.array_equal(per_dst, np.minimum(fanout, n_local))
        for u in range(sh.num_local):
            got = src[dst == u]
            assert len(np.unique(got)) == len(got)
            assert np.all(np.isin(got, _eligible(sh, u, True)))


@pytest.mark.parametrize("ci", [0, 1, 2, 3, "interleaved"])
def test_local_first_rows_match_loop(small_shards, ci):
    """The sampler's row copy is each shard row with its local sources
    moved ahead of the remote ones, order within each kind kept."""
    from repro.graphs.sampler import _local_first_csr
    sh = _interleaved_shard() if ci == "interleaved" else small_shards[0][ci]
    indptr, nbrs, n_local = _local_first_csr(sh)
    assert np.array_equal(indptr, sh.indptr)
    for u in range(sh.num_local):
        r = sh.indices[sh.indptr[u]: sh.indptr[u + 1]]
        want = np.concatenate([r[r < sh.num_local], r[r >= sh.num_local]])
        assert np.array_equal(nbrs[indptr[u]: indptr[u + 1]], want)
        assert n_local[u] == np.count_nonzero(r < sh.num_local)


@pytest.mark.parametrize("ci", [0, 1, 2, 3])
def test_sampling_leaves_shard_arrays_untouched(small_shards, ci):
    sh = small_shards[0][ci]
    before = (sh.indptr.tobytes(), sh.indices.tobytes(), sh.indices.dtype)
    s = NeighborSampler(sh, 3, 3, batch_size=16, seed=2)
    for mb in s.epoch():
        pass
    s.sample_batch(sh.train_vertices()[:8])
    assert (sh.indptr.tobytes(), sh.indices.tobytes(),
            sh.indices.dtype) == before


def _benchmark_rule_check():
    """The benchmark's check of the sampler's choices, loaded by path
    (it imports nothing of the program)."""
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1]
            / "perfbench" / "yardstick" / "reference.py")
    spec = importlib.util.spec_from_file_location("_bench_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("fanout,L", [(2, 2), (3, 2), (5, 3)])
@pytest.mark.parametrize("ci", [0, 3])
def test_sampler_passes_benchmark_rule_check(small_graph, small_shards,
                                             fanout, L, ci):
    """Graph edges, each once, min(fanout, eligible) per local dst, layer
    1 reads local features only, the last layer's outputs are the seeds."""
    ref = _benchmark_rule_check()
    shards, part = small_shards
    g = small_graph
    gi = ref.GraphIndex(np.asarray(g.indptr), np.asarray(g.indices),
                        np.asarray(part), len(shards))
    sh = shards[ci]
    s = NeighborSampler(sh, fanout, L, batch_size=16, seed=9)
    for mb in s.epoch():
        batch = {"blocks": [{k: getattr(b, k) for k in
                             ("edge_src", "edge_dst", "edge_mask",
                              "dst_mask")} for b in mb.blocks],
                 "input_ids": mb.input_ids, "seeds": mb.seeds,
                 "seed_mask": mb.seed_mask}
        ref.block_to_edges(batch, np.asarray(sh.global_ids), client=ci,
                           gi=gi, fanout=fanout,
                           retention=int(gi.deg.max()),
                           train_mask=np.asarray(g.train_mask))


def test_sampler_counters_count_draws(small_shards):
    from repro.obsv.metrics import REGISTRY
    names = ("sampler.vertices_drawn", "sampler.vertices_subsampled")
    sh = small_shards[0][0]
    fanout = 2
    frontier = np.arange(len(sh.global_ids))     # locals, then remotes
    s = NeighborSampler(sh, fanout, 1, batch_size=len(frontier), seed=4)
    n_local = np.array([len(_eligible(sh, u, True))
                        for u in range(sh.num_local)])
    before = REGISTRY.snapshot("sampler.")
    s.sample_batch(frontier)     # one hop, local-only; remotes skipped
    after = REGISTRY.snapshot("sampler.")
    drawn, sub = (after[n] - before[n] for n in names)
    assert drawn == np.count_nonzero(n_local > 0)
    assert sub == np.count_nonzero(n_local > fanout)
    # a whole epoch over three hops
    s = NeighborSampler(sh, fanout, 3, batch_size=16, seed=4)
    before = REGISTRY.snapshot("sampler.")
    for mb in s.epoch():
        pass
    after = REGISTRY.snapshot("sampler.")
    drawn, sub = (after[n] - before[n] for n in names)
    assert 0 < sub <= drawn
