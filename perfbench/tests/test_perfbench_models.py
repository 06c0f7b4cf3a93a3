"""The seam between the round driver and a configuration's model
(``yardstick/models/<conv>.py``): the driver's work counts and the
reference's gaps equal those the driver gave before GraphConv moved
into its module; a second model comes in as one new module, and a new
model's cell as new files and entries alone, its shape arguments
reaching the trainer through the module; an unknown ``conv`` fails by
name; ``calibrate.py`` runs the driver its cell's traffic names."""

import json
import pathlib
import re
import shutil
import sys
import types
import uuid

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


def _recording_trainer(monkeypatch) -> list[dict]:
    """The keyword arguments of each trainer the driver builds, recorded
    on their way in; the fixture's ``root_weight``, which the program's
    trainer does not take, goes no further."""
    import repro.core
    real = repro.core.FederatedGNNTrainer
    got = []

    def make(*args, **kwargs):
        got.append(dict(kwargs))
        kwargs.pop("root_weight", None)
        return real(*args, **kwargs)
    monkeypatch.setattr(repro.core, "FederatedGNNTrainer", make)
    return got


def _sageconv_verdict(monkeypatch, models_dir):
    monkeypatch.setattr(harness, "MODELS", models_dir)
    made = _recording_trainer(monkeypatch)
    cfg = _cfg("reddit-8k", **TINY, conv="sageconv")
    tr, seen, graph, params0 = fr.build(cfg, 2 ** 33 + 5)
    assert set(tr.params[0]) == {"w_self", "w_neigh", "b"}
    assert [m["root_weight"] for m in made] == \
        [harness.model_of("sageconv").SELF_TERM]
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


def test_a_new_model_needs_new_files_only(monkeypatch, tmp_path):
    """A copy of the benchmark takes a model as new files alone: its
    module, a configuration that names it, a limits file and a cell,
    whose name is appended to the ``workloads`` lists of ``round_s`` and
    of every per-layer metric.  The harness finds the cell and every
    metric for it, the set-up round reads ``correct`` true, and the
    trainer gets the module's ``program_args``, a shape argument the
    driver does not name among them."""
    base = tmp_path / "perfbench"
    shutil.copytree(ROOT / "perfbench", base,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    models = base / "yardstick" / "models"
    shutil.copy(FIXTURE_MODELS / "sageconv.py", models / "sageconv.py")
    cfg = {**_cfg("reddit-8k", **TINY, conv="sageconv"),
           "name": "sage-tiny"}
    (base / "configs" / "sage-tiny.json").write_text(json.dumps(cfg))
    (base / "limits" / "sage-train.json").write_text(
        json.dumps(_limits()))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "sage-tiny", "source": "test",
                             "file": "perfbench/configs/sage-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "sage-train", "config": "sage-tiny",
                               "traffic": "rounds", "chips": 1,
                               "why": "test"})
    round_s = [m for m in bench["end_to_end"] if m["name"] == "round_s"]
    for m in round_s + bench["per_layer"]:
        m["workloads"].append("sage-train")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = harness.load_json(tmp_path / "BENCHMARK.json")
    files = harness.cell_files(loaded, "sage-train", base)
    assert files["config"] == cfg and files["limits"] == _limits()
    assert harness.driver_of(files["traffic"]) is fr
    layer = harness.metrics_of(loaded, "sage-train", "per_layer")
    assert [m["name"] for m in layer] == \
        [m["name"] for m in loaded["per_layer"]]
    assert all((base / "metrics" / f"{m['name']}.py").is_file()
               for m in layer)
    assert "round_s" in [m["name"] for m in
                         harness.metrics_of(loaded, "sage-train",
                                            "end_to_end")]

    monkeypatch.setattr(harness, "MODELS", models)
    made = _recording_trainer(monkeypatch)
    tr, seen, graph, params0 = fr.build(files["config"], 2 ** 33 + 7)
    del tr
    ok, shown = fr.check(seen, files["config"], graph, params0,
                         files["limits"])
    assert ok, shown
    args = harness.model_of("sageconv").program_args(files["config"])
    assert "root_weight" in args
    assert len(made) == 1 and made[0].items() >= args.items()


def test_an_unknown_conv_fails_by_name(monkeypatch):
    """A name with spaces and a fresh uuid, which no model module has;
    the message lists every module there is, and no trainer is built."""
    conv = f"no such model {uuid.uuid4().hex}"
    known = sorted(p.stem for p in harness.MODELS.glob("*.py"))
    built = []
    monkeypatch.setattr(fr, "trainer", lambda *a: built.append(a))
    with pytest.raises(harness.NoModel,
                       match=re.escape(repr(conv)) + ".*; known: "
                       + re.escape(repr(known)) + "$"):
        fr.build(_cfg("reddit-8k", **TINY, conv=conv), 1)
    assert built == []


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
