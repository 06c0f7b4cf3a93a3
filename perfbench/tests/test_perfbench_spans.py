"""The readers of the program's host spans, and the bridge that puts
those spans on the profiler's clock beside the device ops."""

import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import harness  # noqa: E402
from perfbench.yardstick import trace  # noqa: E402

#: each new reader and the span it sums
READS = {"train.sample_s": "client.sample",
         "train.step_inputs_s": "client.step_inputs",
         "train.step_dispatch_s": "client.step_dispatch",
         "train.apply_push_s": "round.apply_push",
         "train.compile_s": "jit.compile"}


def _reader(metric):
    return harness.load_module(ROOT / "perfbench" / "metrics" /
                               f"{metric}.py")


def _ctx(name):
    """Two traced rounds, [10, 20] and [20, 30] s, with three spans of
    ``name`` in them, one before them and one of another name."""
    return {"rounds": [(10.0, 20.0), (20.0, 30.0)],
            "spans": [(name, 11.0, 0.5), (name, 19.0, 0.25),
                      (name, 25.0, 1.25), (name, 5.0, 7.0),
                      ("client.pull", 12.0, 3.0)]}


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_sums_its_span_per_round(metric):
    got = _reader(metric).read(_ctx(READS[metric]))
    assert got == pytest.approx((0.5 + 0.25 + 1.25) / 2)


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_reads_nothing_from_a_program_without_the_span(
        metric, monkeypatch):
    """A program older than the span, with other spans in the rounds."""
    ctx = _ctx("client.train_epoch")
    if metric == "train.compile_s":
        # compiles are rare, so what tells this program apart is that it
        # counts no compiles
        from repro.obsv import metrics
        assert _reader(metric).read(ctx) == 0.0
        monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    assert _reader(metric).read(ctx) is None


def test_spans_land_on_the_profiler_clock(tmp_path):
    """Three program spans recorded under a profiler session appear on a
    host plane under their own names, with their durations, and at one
    clock offset from the recorder's own starts."""
    import jax
    from repro.obsv.trace import TraceRecorder
    rec = TraceRecorder()
    rec.enable()
    names = ("bridge.first_s", "bridge.second_s", "bridge.third_s")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for name in names:
            with rec.span(name):
                time.sleep(0.005)
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    events = trace.load(str(tmp_path))
    offsets = []
    for name, (_, _, _, t0, dur, _) in zip(names, rec.events):
        marks = trace.host_spans(events, name)
        assert len(marks) == 1, name
        start, end = marks[0]
        assert dur >= 0.005
        assert (end - start) * 1e-9 == pytest.approx(dur, abs=1e-3)
        offsets.append(start - t0 * 1e9)
    assert max(offsets) - min(offsets) <= 1e6          # 1 ms, in ns


def test_compile_span_lands_on_the_profiler_clock(tmp_path):
    """A compile inside a profiler session, with tracing on, is one
    ``jit.compile`` span in the recorder and one on a host plane of the
    xplane, of about the same length."""
    import jax
    import numpy as np
    from repro.obsv import trace as program_trace
    program_trace.install_jax_hooks()
    rec = program_trace.TRACE
    rec.disable()
    rec.clear()

    def bridged_compile(x):
        return x * 5.0 - 2.0

    jax.profiler.start_trace(str(tmp_path))
    rec.enable()
    try:
        jax.block_until_ready(jax.jit(bridged_compile)(np.ones(3, np.float32)))
    finally:
        rec.disable()
        jax.profiler.stop_trace()
    got = [e for e in rec.events if e[0] == "jit.compile"]
    rec.clear()
    rec.context.clear()
    assert len(got) == 1 and "bridged_compile" in got[0][5]["fun"]
    marks = trace.host_spans(trace.load(str(tmp_path)), "jit.compile")
    assert len(marks) == 1
    assert (marks[0][1] - marks[0][0]) * 1e-9 == pytest.approx(got[0][4],
                                                              abs=1e-3)
