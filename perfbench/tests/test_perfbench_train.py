"""The training cells' check on the CPU at a tiny size: a sound run is
correct, the control (the reference at ``high``, three bf16 passes, in
the program's place) is not, a run with the timed path broken
underneath is not, once for each fault the cell can have, and neither
is the reference with each of the calibration's faults planted."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import calibrate, harness  # noqa: E402
from perfbench.drivers import federated_rounds as fr  # noqa: E402
from perfbench.yardstick import compare  # noqa: E402

TINY = {"vertices": 800, "avg_degree": 30.0, "classes": 5, "features": 24,
        "hidden": 8, "graph_seed": 3, "eval_max_edges": 4000, "batch": 16}


def _cfg():
    cfg = json.loads((ROOT / "perfbench" / "configs" /
                      "reddit-8k.json").read_text())
    return {**cfg, **TINY}


def _limits(cell="reddit-train"):
    return json.loads((ROOT / "perfbench" / "limits" /
                       f"{cell}.json").read_text())


def _run(seed=2 ** 33 + 5, cell="reddit-train"):
    return fr.run(_cfg(), {}, seed=seed, seconds=0.5, t_start=0.0,
                  limits=_limits(cell), tracer=harness.Tracer(False))


CELLS = ("reddit-train", "arxiv-train")
#: the cells whose limits judge the number a whole-round fault moves;
#: error feedback dropped moves only numbers no cell judges, and one
#: client's model as the average only one that reddit-train judges
#: (PERF.md)
ROUND_FAULT_CELLS = {"push_skipped": CELLS, "ef_dropped": (),
                     "one_client_averaged": ("reddit-train",),
                     "eval_skipped": CELLS, "eval_stale": CELLS}


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["end_to_end"]["round_s"] > 0
    assert set(res["checks"]) == set(compare.NAMES)


@pytest.fixture(scope="module")
def recorded():
    """One tiny set-up round and its reference."""
    cfg = _cfg()
    tr, seen, graph, params0 = fr.build(cfg, 11)
    return cfg, seen, graph, params0, fr.reference_side(seen, cfg, graph,
                                                        params0)


def _control_verdict(recorded, cell):
    cfg, seen, graph, params0, ref = recorded
    control = fr.reference_side(seen, cfg, graph, params0, mode="high")
    return compare.verdict(fr.gaps_of(control, ref, params0), _limits(cell))


def test_control_is_not_correct(recorded):
    ok, shown = _control_verdict(recorded, "reddit-train")
    assert not ok, shown


def test_control_is_not_correct_arxiv(recorded):
    ok, shown = _control_verdict(recorded, "arxiv-train")
    assert not ok, shown


def _unchanged_state(monkeypatch):
    import repro.core.federated as fed
    from repro.optim import Optimizer
    real = fed.adam

    def frozen(lr):
        opt = real(lr)
        return Optimizer("frozen", opt.init, lambda p, g, s: (p, s))
    monkeypatch.setattr(fed, "adam", frozen)


def _half_batch(monkeypatch):
    import jax.numpy as jnp
    from repro.models import gnn
    real = gnn.loss_fn

    def half(params, batch, features, caches, labels, *, conv):
        m = batch["seed_mask"]
        n = jnp.sum(m)
        keep = jnp.arange(m.shape[0]) < (n + 1) // 2
        return real(params, {**batch, "seed_mask": m & keep}, features,
                    caches, labels, conv=conv)
    monkeypatch.setattr(gnn, "loss_fn", half)


def _no_exchange(monkeypatch):
    import numpy as np
    from repro.exchange.client import ExchangeClient
    real = ExchangeClient.peek

    def zeros(self, gids, layers=None):
        return [np.zeros_like(v) for v in real(self, gids, layers)]
    monkeypatch.setattr(ExchangeClient, "peek", zeros)


def _altered(monkeypatch):
    from repro.exchange.codec import Int8Codec
    real = Int8Codec.decode
    monkeypatch.setattr(Int8Codec, "decode",
                        lambda self, p: real(self, p) * 1.05)


def _push_skipped(monkeypatch):
    from repro.core.federated import FederatedGNNTrainer
    monkeypatch.setattr(FederatedGNNTrainer, "_compute_push",
                        lambda self, ci, params: (None, 0.0, 0.0))


def _eval_skipped(monkeypatch):
    from repro.core.federated import FederatedGNNTrainer
    monkeypatch.setattr(
        FederatedGNNTrainer, "evaluate",
        lambda self, params=None: self.acc_history[-1]
        if self.acc_history else 0.0)


def _one_client_averaged(monkeypatch):
    import repro.core.federated as fed
    monkeypatch.setattr(fed, "fedavg_leaves",
                        lambda leaves, weights: leaves[0])


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "altered_answer": _altered,
          "push_skipped": _push_skipped, "eval_skipped": _eval_skipped,
          "one_client_averaged": _one_client_averaged}


def _cases(faults):
    """(fault, cell) pairs; a reddit-train case keeps the bare fault name
    as its id."""
    return [pytest.param(f, c, id=f if c == "reddit-train" else f"{f}-{c}")
            for f in faults for c in ROUND_FAULT_CELLS.get(f, CELLS)]


@pytest.mark.parametrize("fault,cell", _cases(FAULTS))
def test_broken_timed_path_is_not_correct(monkeypatch, fault, cell):
    FAULTS[fault](monkeypatch)
    res = _run(seed=7, cell=cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault,cell", _cases(sorted(calibrate.FAULTS)))
def test_planted_fault_is_not_correct(recorded, fault, cell):
    cfg, seen, graph, params0, ref = recorded
    bad = fr.reference_side(seen, cfg, graph, params0,
                            plant=calibrate.FAULTS[fault])
    ok, shown = compare.verdict(fr.gaps_of(bad, ref, params0),
                                _limits(cell))
    assert not ok, shown
