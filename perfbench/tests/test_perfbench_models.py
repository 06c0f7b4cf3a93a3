"""The seam between the round driver and a configuration's model
(``yardstick/models/<conv>.py``): the driver's work counts and the
reference's gaps equal those the driver gave before GraphConv moved
into its module; a second model comes in as one new module; an unknown
``conv`` fails by name; ``calibrate.py`` runs the driver its cell's
traffic names."""

import json
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import calibrate, harness  # noqa: E402
from perfbench.drivers import federated_rounds as fr  # noqa: E402
from perfbench.yardstick import compare  # noqa: E402

FIXTURE_MODELS = pathlib.Path(__file__).with_name("fixtures") / "models"
TINY = {"vertices": 800, "avg_degree": 30.0, "classes": 5, "features": 24,
        "hidden": 8, "graph_seed": 3, "eval_max_edges": 4000, "batch": 16}


def _cfg(name, **over):
    cfg = json.loads((ROOT / "perfbench" / "configs" /
                      f"{name}.json").read_text())
    return {**cfg, **over}


def _limits(cell="reddit-train"):
    return json.loads((ROOT / "perfbench" / "limits" /
                       f"{cell}.json").read_text())


#: ``counts`` of the driver before the model moved out of it, on small
#: graphs of each configuration at its own widths, batch and fanout
COUNTS = {
    "reddit-8k": ({"vertices": 600, "avg_degree": 40.0},
                  {"steps_per_round": 24, "flops_per_round": 982397280.0,
                   "codec_bytes_per_round": 1135536.0}),
    "arxiv-16k": ({"vertices": 800},
                  {"steps_per_round": 24, "flops_per_round": 278697984.0,
                   "codec_bytes_per_round": 1130944.0}),
}


@pytest.mark.parametrize("config", sorted(COUNTS))
def test_counts_equal_those_before_the_model_moved(config):
    over, want = COUNTS[config]
    cfg = _cfg(config, **over)
    tr, _ = fr.trainer(cfg, 1)
    assert fr.counts(tr, cfg) == want


#: gaps of the program and of the control against the reference on
#: reddit-8k at TINY, seed 11, as the driver gave them before the model
#: moved out of it (CPU)
GAPS = {
    "program": {"loss_gap": 1.0967156218585952e-07,
                "grad_gap": 9.209222708513252e-08,
                "update_gap": 6.392868779456471e-08,
                "cache_gap": 4.0494105020044073e-07,
                "push_gap": 0.0, "push_stale": 0.0,
                "fedavg_gap": 2.2398002757171449e-07, "acc_gap": 0.0},
    "control": {"loss_gap": 0.0006263971471833122,
                "grad_gap": 0.0010555704537671417,
                "update_gap": 0.010143333222955247,
                "cache_gap": 1.6039569378259608e-05,
                "push_gap": 0.09284208059981255, "push_stale": 0.0,
                "fedavg_gap": 0.02658690987703256, "acc_gap": 0.0},
}


def test_reference_gaps_equal_those_before_the_model_moved():
    cfg = _cfg("reddit-8k", **TINY)
    tr, seen, graph, params0 = fr.build(cfg, 11)
    del tr
    ref = fr.reference_side(seen, cfg, graph, params0)
    got = {"program": fr.gaps_of(fr.program_side(seen, cfg), ref, params0),
           "control": fr.gaps_of(fr.reference_side(
               seen, cfg, graph, params0, mode="high"), ref, params0)}
    assert got == GAPS


def _sageconv_verdict(monkeypatch, models_dir):
    monkeypatch.setattr(harness, "MODELS", models_dir)
    cfg = _cfg("reddit-8k", **TINY, conv="sageconv")
    tr, seen, graph, params0 = fr.build(cfg, 2 ** 33 + 5)
    assert set(tr.params[0]) == {"w_self", "w_neigh", "b"}
    del tr
    return fr.check(seen, cfg, graph, params0, _limits())


def test_a_second_model_comes_in_as_one_module(monkeypatch):
    ok, shown = _sageconv_verdict(monkeypatch, FIXTURE_MODELS)
    assert ok, shown


def test_the_second_model_without_its_self_term_is_not_correct(
        monkeypatch, tmp_path):
    text = (FIXTURE_MODELS / "sageconv.py").read_text()
    assert "SELF_TERM = True" in text
    (tmp_path / "sageconv.py").write_text(
        text.replace("SELF_TERM = True", "SELF_TERM = False"))
    ok, shown = _sageconv_verdict(monkeypatch, tmp_path)
    assert not ok, shown


def test_an_unknown_conv_fails_by_name():
    with pytest.raises(harness.NoModel,
                       match=r"'gat'.*known: \['graphconv'\]"):
        fr.build(_cfg("reddit-8k", **TINY, conv="gat"), 1)


def test_calibrate_runs_the_driver_its_traffic_names(monkeypatch, capsys):
    calls = []
    gap = {"program": 0.25, "control": 1.0}

    def build(cfg, seed):
        calls.append(("build", cfg["name"], seed))
        return None, {"seed": seed}, {}, []

    def reference_side(seen, cfg, graph, params0, *, mode="highest",
                       plant=None):
        fault = next((f for f, p in calibrate.FAULTS.items() if p is plant),
                     None)
        calls.append(("reference", mode, fault))
        return {"seed": seen["seed"], "kind": fault or
                ("control" if mode == "high" else "reference")}

    def gaps_of(side, ref, params0):
        value = gap.get(side["kind"], 2.0) * side["seed"]
        return {name: value for name in compare.NAMES}

    stub = types.ModuleType("perfbench.drivers.stub_rounds")
    stub.build = build
    stub.reference_side = reference_side
    stub.program_side = lambda seen, cfg: {**seen, "kind": "program"}
    stub.gaps_of = gaps_of
    monkeypatch.setitem(sys.modules, "perfbench.drivers.stub_rounds", stub)
    monkeypatch.setattr(harness, "cell_files", lambda bench, workload: {
        "cell": {"name": workload, "chips": 1},
        "config": {"name": "stub-config"},
        "traffic": {"driver": "stub_rounds"}, "limits": {}})
    monkeypatch.setattr(harness, "configure_jax", lambda: None)
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})

    assert calibrate.main(["--workload", "stub-train", "--seeds", "3", "5",
                           "--faults", "1"]) == 0
    assert calls[:2] == [("build", "stub-config", 3),
                         ("reference", "highest", None)]
    assert [c[2] for c in calls if c[0] == "reference" and c[2]] \
        == list(calibrate.FAULTS)
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["workload"] == "stub-train" and summary["seeds"] == 2
    for name in compare.NAMES:
        assert summary[name] == {
            "lower": 1.25, "upper_control": 3.0,
            **{f"upper_{f}": 6.0 for f in calibrate.FAULTS}}
