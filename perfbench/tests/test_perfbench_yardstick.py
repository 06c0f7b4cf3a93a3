"""The benchmark's yardstick on the CPU: the generator, the FLOP and byte
counts, the trace reduction, the harness's discovery of files by name,
and the refusal to run off the chip."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import harness  # noqa: E402
from perfbench.yardstick import flops, graphgen, peaks, trace  # noqa: E402

graphconv = harness.model_of("graphconv")

TINY = {"graph_seed": 3, "vertices": 600, "avg_degree": 40.0, "classes": 6,
        "features": 20, "train_frac": 0.5, "homophily": 0.9,
        "intra_pair_cap": 0.9, "feature_noise": 1.0}


def test_generator_counts_are_exact_and_seed_only_moves_values():
    st = graphgen.structure(TINY)
    r = graphgen.realised(st)
    assert r["vertices"] == 600
    assert r["directed_edges"] == 2 * round(600 * 40.0 / 2)
    assert r["train_vertices"] == 300
    assert np.all(np.bincount(st["labels"]) == 100)
    dst = np.repeat(np.arange(600), np.diff(st["indptr"]))
    src = st["indices"].astype(np.int64)
    assert np.all(src != dst)
    key = dst * 600 + src
    assert len(np.unique(key)) == len(key)           # no parallel edges
    assert np.all(np.diff(key) > 0)                  # rows sorted
    rev = np.sort(src * 600 + dst)
    assert np.array_equal(rev, np.sort(key))         # symmetric
    # 100 vertices a class: 4,950 pairs each, far above the 90% share
    # asked for, so the cap does not bind and homophily is as asked
    assert r["edge_homophily"] == pytest.approx(0.9, abs=1e-3)
    again = graphgen.structure(TINY)
    assert np.array_equal(again["indices"], st["indices"])
    f1 = graphgen.features(TINY, st["labels"], 2 ** 33 + 1)
    f2 = graphgen.features(TINY, st["labels"], 2 ** 33 + 1)
    f3 = graphgen.features(TINY, st["labels"], 1)
    assert f1.shape == (600, 20) and f1.dtype == np.float32
    assert np.array_equal(f1, f2) and not np.array_equal(f1, f3)


def test_generator_cap_moves_degree_across_classes():
    cfg = {**TINY, "vertices": 120, "avg_degree": 60.0}
    st = graphgen.structure(cfg)
    r = graphgen.realised(st)
    assert r["directed_edges"] == 120 * 60
    # 20 a class: 190 pairs, 90% of them is 171 a class, 1,026 in all,
    # against 0.9 x 3,600 asked for; the rest crosses classes
    assert st["intra_edges"] == 1026
    assert r["edge_homophily"] == pytest.approx(1026 / 3600)


def test_hop_sizes_and_step_flops():
    assert flops.hop_sizes(64, 5, 3, 10_000) == [64, 384, 2304, 10_000]
    assert flops.hop_sizes(64, 5, 3, 100) == [64, 100, 100, 100]
    got = graphconv.train_step_flops(batch=2, fanout=1, widths=[3, 4, 5],
                                     shard_vertices=100)
    # layer 1: 4 dst, 3 -> 4; layer 2: 2 dst, 4 -> 5
    l1 = 2 * (2 * 4 * 3 * 4) + 2 * (4 * 3 * 3)
    l2 = 3 * (2 * 2 * 4 * 5) + 2 * (2 * 3 * 4)
    assert got == l1 + l2


def test_int8_codec_bytes():
    # 10 rows of 32: quantize 10*(128 + 36), dequantize the same
    assert flops.int8_codec_bytes(10, 32) == 10 * 2 * (32 * 5 + 4)


def test_peaks_table_refuses_unknown_devices():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_union_and_gaps():
    busy, gaps = trace.union([(10, 20), (15, 30), (40, 50), (0, 5)], 0, 60)
    assert busy == 5 + 20 + 10
    assert gaps == [(5, 10), (30, 40), (50, 60)]
    busy, gaps = trace.union([(0, 100)], 10, 20)
    assert (busy, gaps) == (10, [])


FIXTURE = pathlib.Path(__file__).with_name("fixtures") / "v5e_trace.json"


def test_reduce_recorded_trace():
    """A trace recorded on a v5e chip: three ``bench.round`` marks, each
    around a jitted matmul and an int8 quantize/dequantize."""
    events = [tuple(e) for e in json.loads(FIXTURE.read_text())]
    marks = trace.host_spans(events, "bench.round")
    assert len(marks) == 3
    lo, hi = marks[0][0], marks[-1][1]
    spans = [("codec", m[0], m[1]) for m in marks]
    red = trace.reduce(events, window=(lo, hi), spans=spans)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    # the quantize/dequantize programs, three calls each in the window
    mods = {n.split("(")[0]: v for n, v in red["modules"].items()}
    assert mods["jit_quantize_padded"] == pytest.approx(
        (10964 + 10913 + 10906) * 1e-9)
    assert mods["jit_dequantize_padded"] == pytest.approx(
        (5959 + 5982 + 6195) * 1e-9)
    ops = dict(red["device_ops"])
    assert ops["quantize_padded.1 = (s8[4096,128], f32[4096,1]) "
               "custom-call"] > ops["dequantize_padded.1 = f32[4096,128] "
                                    "custom-call"] > 0
    # ops do not overlap on this trace: their sum is the busy time
    assert sum(ops.values()) == pytest.approx(red["busy_s"])
    labels = dict(red["idle_gaps"])
    assert list(labels) == ["codec"]
    assert labels["codec"] == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)


def test_harness_finds_files_it_was_not_told_about(tmp_path):
    """A new metric, cell, configuration and traffic added as files plus
    entries of BENCHMARK.json are found by name, with no code edited."""
    base = tmp_path / "perfbench"
    shutil.copytree(ROOT / "perfbench", base,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "perfbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-train", "config": "tiny",
                               "traffic": "tiny-rounds", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "train.made_up_s", "unit": "s",
                               "better": "lower", "source": "program_span",
                               "layer": "test", "moves": "round_s",
                               "workloads": ["tiny-train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (base / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (base / "traffic" / "tiny-rounds.json").write_text(
        json.dumps({"driver": "federated_rounds"}))
    (base / "limits" / "tiny-train.json").write_text(json.dumps({}))
    (base / "metrics" / "train.made_up_s.py").write_text(
        "def read(ctx):\n    return 2.5 * len(ctx['rounds'])\n")

    loaded = harness.load_json(tmp_path / "BENCHMARK.json")
    files = harness.cell_files(loaded, "tiny-train", base)
    assert files["config"] == TINY
    assert files["traffic"]["driver"] == "federated_rounds"
    layer = harness.metrics_of(loaded, "tiny-train", "per_layer")
    assert [m["name"] for m in layer] == ["train.made_up_s"]
    got = harness.read_layer_metrics(layer, {"rounds": [(0, 1), (1, 2)]},
                                     base)
    assert got == {"train.made_up_s": {"value": 5.0, "unit": "s"}}
    e2e = harness.metrics_of(loaded, "tiny-train", "end_to_end")
    assert [m["name"] for m in e2e] == ["setup_s"]


def test_every_named_file_exists():
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        harness.cell_files(bench, w["name"])
    for m in bench["per_layer"]:
        assert (ROOT / "perfbench" / "metrics" / f"{m['name']}.py").exists()
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()


def test_run_refuses_a_host_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         "reddit-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert "found no TPU" in proc.stderr
    assert proc.stdout.strip() == ""
