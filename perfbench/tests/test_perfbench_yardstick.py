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


def _label_gaps_by_scan(gaps, spans, default):
    """The oracle: every span scanned for each gap, the label of the
    least (length, name) among those that cover the gap's middle."""
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(b - a, n) for n, a, b in spans if a <= mid <= b]
        out.append((min(cover)[1] if cover else default, (e - s) * 1e-9))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2, 2 ** 33 + 3])
def test_label_gaps_sweep_equals_the_scan(seed):
    """Whole-number times on a short axis, so that lengths and names tie
    and middles fall on spans' edges; nested spans, spans of no length,
    and gaps beyond every span."""
    rng = np.random.default_rng(seed)
    spans = []
    for _ in range(400):
        a = float(rng.integers(0, 1000))
        spans.append((str(rng.choice(list("abc"))), a,
                      a + float(rng.integers(0, 40))))
    spans += [("outer", 100.0, 900.0), ("inner", 400.0, 420.0),
              ("inner", 400.0, 420.0), ("a", 400.0, 420.0),
              ("b", 405.0, 415.0), ("point", 410.0, 410.0)]
    rng.shuffle(spans)
    starts = rng.integers(0, 1200, 600)
    gaps = [(float(a), float(a + d))
            for a, d in zip(starts, rng.integers(0, 21, 600))]
    gaps += [(409.0, 411.0), (1500.0, 1600.0)]
    got = trace.label_gaps(gaps, spans, "none")
    assert got == _label_gaps_by_scan(gaps, spans, "none")
    labels = [n for n, _ in got]
    assert "none" in labels and "point" in labels and len(set(labels)) > 4


def _device_xspace(ops, modules, lo: int) -> bytes:
    """Two TPU planes as a serialized XSpace: ``/device:TPU:0`` with
    ``ops`` and ``modules`` (``(name, start_ns, end_ns)``, whole ns) on
    their lines and two events on a line the reduction does not read;
    ``/device:TPU:1`` with one event on such a line and no ops."""
    from jax.profiler import ProfileData
    names = sorted({n for n, _, _ in ops + modules} | {"step 1"})
    ids = {n: i + 1 for i, n in enumerate(names)}

    def line(i, name, events):
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {(a - lo) * 1000} "
            f"duration_ps: {(b - a) * 1000} }}" for n, a, b in events)
        return f'lines {{ id: {i} name: "{name}" timestamp_ns: {lo} {evs} }}'

    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}' for n, i in ids.items())
    steps = [("step 1", lo, lo + 10), ("step 1", lo + 20, lo + 30)]
    text = (f'planes {{ id: 900 name: "/device:TPU:0" '
            f'{line(1, trace.OPS_LINE, ops)} '
            f'{line(2, trace.MODULES_LINE, modules)} '
            f'{line(3, "Steps", steps)} {meta} }} '
            f'planes {{ id: 901 name: "/device:TPU:1" '
            f'{line(1, "Steps", steps[:1])} {meta} }}')
    return ProfileData.text_proto_to_serialized_xspace(text)


def test_filtered_load_reduces_as_the_full_load(tmp_path):
    """A profiler trace recorded here, with ``bench.round`` marks and
    other host spans, and two TPU planes added to it whose ops fall
    inside the dispatch spans, so that most of the window is gaps: the
    reduction of the load that keeps only what it reads equals that of
    the load of every event, field for field."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x) * 2.0)
    x = jnp.ones(64)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.round"):
                for _ in range(12):
                    with jax.profiler.TraceAnnotation("client.step_inputs"):
                        y = f(x)
                    with jax.profiler.TraceAnnotation(
                            "client.step_dispatch"):
                        y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    full = trace.load(str(tmp_path))
    names = ("bench.round", "client.step_inputs", "client.step_dispatch")
    spans = [(n, a, b) for n in names for a, b in trace.host_spans(full, n)]
    marks = trace.host_spans(full, "bench.round")
    assert len(marks) == 3 and len(spans) == 3 + 2 * 36

    rng = np.random.default_rng(5)
    lo = int(marks[0][0]) - 1000
    ops, modules = [], []
    for n, a, b in spans:
        if n != "client.step_dispatch":
            continue
        a, b = int(a), max(int(b), int(a) + 40)
        cuts = np.sort(rng.integers(a, b, 6))
        ops += [(f"%fusion.{i} = f32[64]{{0}} fusion(f32[64]{{0}} %p)",
                 int(s), int(e)) for i, (s, e) in
                enumerate(zip(cuts[::2], cuts[1::2]))]
        modules.append(("jit__step(123)", int(cuts[0]), int(cuts[-1])))
    path, = tmp_path.glob("**/*.xplane.pb")
    path.write_bytes(path.read_bytes() + _device_xspace(ops, modules, lo))

    full = trace.load(str(tmp_path))
    kept = trace.load(str(tmp_path), host_names={"bench.round"})
    assert len(kept) < len(full) // 2
    assert trace.device_planes(kept) == trace.device_planes(full) == [
        "/device:TPU:0", "/device:TPU:1"]
    assert trace.host_spans(kept, "bench.round") == marks
    assert trace.host_spans(kept, "client.step_inputs") == []
    window = (marks[0][0], marks[-1][1])
    want = trace.reduce(full, window=window, spans=spans)
    assert trace.reduce(kept, window=window, spans=spans) == want
    assert 0 < want["busy_s"] < want["window_s"] and want["modules"]
    assert {n for n, _ in want["idle_gaps"]} >= {"client.step_inputs"}


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
    # the new cell reads the metrics of every cell and its own, none of
    # those that list other cells
    every_cell = {kind: [m["name"] for m in loaded[kind]
                         if "workloads" not in m]
                  for kind in ("end_to_end", "per_layer")}
    layer = harness.metrics_of(loaded, "tiny-train", "per_layer")
    assert [m["name"] for m in layer] == \
        every_cell["per_layer"] + ["train.made_up_s"]
    got = harness.read_layer_metrics(
        layer[-1:], {"rounds": [(0, 1), (1, 2)]}, base)
    assert got == {"train.made_up_s": {"value": 5.0, "unit": "s"}}
    e2e = harness.metrics_of(loaded, "tiny-train", "end_to_end")
    assert "setup_s" in every_cell["end_to_end"]
    assert [m["name"] for m in e2e] == every_cell["end_to_end"]


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
