"""A client epoch's step inputs, packed on the host and moved in one
host→device transfer (``gnn.epoch_to_arrays``): the same per-step
batches as ``gnn.blocks_to_arrays``, the same round, and counters that
say how many transfers carried how many steps."""

import jax
import numpy as np
import pytest

from repro.core import FederatedGNNTrainer, default_strategies
from repro.graphs import make_graph
from repro.models import gnn
from repro.obsv.metrics import REGISTRY
from repro.obsv.trace import install_jax_hooks

CONVS = ["graphconv", "sageconv"]
COUNTERS = ("train.step_input_transfers", "train.step_input_steps")


@pytest.fixture(scope="module")
def graph():
    return make_graph("arxiv", scale=0.15, seed=7)


def _trainer(graph, conv):
    # batch 16 leaves every client a partial last batch
    return FederatedGNNTrainer(graph, 2, default_strategies()["OP"],
                               conv=conv, batch_size=16, fanout=4, seed=0,
                               epochs_per_round=2)


def _recording(tr):
    """Wraps every sampler's ``epoch`` so the minibatches it hands out
    are kept, one list per epoch and client."""
    seen = {ci: [] for ci in range(tr.k)}

    def wrap(ci, real):
        def epoch(*args, **kwargs):
            got = list(real(*args, **kwargs))
            seen[ci].append(got)
            return iter(got)
        return epoch

    for ci, s in enumerate(tr.samplers):
        s.epoch = wrap(ci, s.epoch)
    return seen


def _assert_same_batch(got, want):
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    assert set(got) == set(want)
    assert len(got["blocks"]) == len(want["blocks"])
    for gb, wb in zip(got["blocks"], want["blocks"]):
        assert set(gb) == set(wb)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("conv", CONVS)
def test_epoch_steps_equal_blocks_to_arrays(graph, conv):
    tr = _trainer(graph, conv)
    for ci, s in enumerate(tr.samplers):
        batches = list(s.epoch())
        last = int(np.count_nonzero(batches[-1].seed_mask))
        assert 0 < last < tr.batch_size, "no partial last batch"
        steps = gnn.epoch_to_arrays(batches)
        assert len(steps) == len(batches) == s.num_batches()
        for mb, got in zip(batches, steps):
            _assert_same_batch(got, gnn.blocks_to_arrays(mb))


def test_pack_epoch_lays_out_every_step_array(graph):
    tr = _trainer(graph, "graphconv")
    batches = list(tr.samplers[0].epoch())
    packed, layout = gnn.pack_epoch(batches)
    assert packed.dtype == np.int32
    assert packed.shape == (len(batches), sum(int(np.prod(e[3]))
                                              for e in layout))
    assert len(layout) == 6 * len(batches[0].blocks) + 3
    ends = [off + int(np.prod(shape)) for _, _, off, shape, _ in layout]
    assert [e[2] for e in layout] == [0] + ends[:-1]
    for (blk, key, off, shape, is_bool) in layout:
        host = getattr(batches[-1] if blk is None
                       else batches[-1].blocks[blk], key)
        assert is_bool == (host.dtype == np.bool_)
        np.testing.assert_array_equal(
            packed[-1, off: off + host.size], host.reshape(-1).astype(int))


def test_pack_epoch_refuses_an_epoch_of_two_layouts(graph):
    tr = _trainer(graph, "graphconv")
    a = next(iter(tr.samplers[0].epoch()))
    b = next(iter(tr.samplers[1].epoch()))
    if a.blocks[-1].edge_src.shape == b.blocks[-1].edge_src.shape:
        b.blocks[-1].edge_src = np.zeros(a.blocks[-1].edge_src.size + 1,
                                         np.int64)
    with pytest.raises(ValueError, match="laid out for"):
        gnn.pack_epoch([a, b])
    assert gnn.epoch_to_arrays([]) == []


@pytest.mark.parametrize("conv", CONVS)
def test_client_round_equals_a_loop_of_blocks_to_arrays(graph, conv):
    tr = _trainer(graph, conv)
    tr.pretrain_round()
    seen = _recording(tr)
    inner, losses = tr._train_step, []

    def step(*args):
        out = inner(*args)
        losses.append(np.asarray(out[2]))
        return out

    tr._train_step = step
    res = tr.client_round(0)
    tr._train_step = inner

    params, opt_state, want = tr.params, tr.opt.init(tr.params), []
    for mb in (mb for ep in seen[0] for mb in ep):
        params, opt_state, loss = inner(
            params, opt_state, gnn.blocks_to_arrays(mb), tr.feats[0],
            tr._caches[0], tr.labels[0])
        want.append(np.asarray(loss))
    assert len(losses) == sum(len(ep) for ep in seen[0]) > 0
    np.testing.assert_array_equal(np.stack(losses), np.stack(want))
    for got, exp in zip(jax.tree_util.tree_leaves(res.params),
                        jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.fixture(scope="module")
def two_rounds(graph):
    """A trainer's first round and its second, with the counters and
    ``jit.compiles`` read around each."""
    install_jax_hooks()
    tr = _trainer(graph, "graphconv")
    tr.pretrain_round()
    seen = _recording(tr)
    snaps = [REGISTRY.snapshot()]
    for r in range(2):
        tr.run_round(r, 0.0)
        snaps.append(REGISTRY.snapshot())
    return tr, seen, snaps


@pytest.mark.parametrize("rnd", [0, 1])
def test_round_counts_one_transfer_per_client_epoch(two_rounds, rnd):
    tr, seen, snaps = two_rounds
    before, after = snaps[rnd], snaps[rnd + 1]
    transfers, steps = (after[n] - before.get(n, 0) for n in COUNTERS)
    assert transfers == tr.k * tr.epochs
    assert steps == sum(len(seen[ci][rnd * tr.epochs + e])
                        for ci in range(tr.k) for e in range(tr.epochs))
    assert steps == tr.epochs * sum(s.num_batches() for s in tr.samplers)


def test_second_round_compiles_nothing(two_rounds):
    _, _, snaps = two_rounds
    assert snaps[1]["jit.compiles"] > snaps[0]["jit.compiles"]
    assert snaps[2]["jit.compiles"] == snaps[1]["jit.compiles"]
