"""Observability plane: histograms, spans, wire telemetry, merges.

Gated invariants:

  * histogram bucket math is exact (bisect on precomputed bounds, not
    floating logs): boundary values, overflow, count conservation,
    snapshot/delta arithmetic, text exposition
  * disabled tracing is a true no-op: the shared noop span object, zero
    recorded events, ring capacity bounded when enabled
  * a traced federated round records its host-work spans (sampling,
    step inputs, step dispatch, push commit, compiles) and the step
    spans sum to no more than their epoch; an untraced round records
    nothing; every compile bumps ``jit.compiles``
  * Chrome trace-event export is valid and merging is deterministic —
    same snapshots in, byte-identical JSON out, distinct synthetic pids
    even for same-OS-process sources
  * all three TCP server types (embed shard, fedsvc coordinator,
    gnnserve frontend) answer the shared OP_METRICS/OP_TRACE opcodes on
    their existing data ports, as does the worker's telemetry-only
    listener; obs_dump merges the scrapes into one timeline + table
  * TcpTransport RPC samples feed the registry histograms through one
    bookkeeping point while preserving the deque API calibration uses
  * gnnserve OP_SSTATS is registry-backed (cache hit-rate, per-depth
    exits, gnnserve.* metrics section)
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.exchange import wire
from repro.exchange.socket_transport import TcpTransport
from repro.launch import obs_dump
from repro.launch.embed_server import serve_in_thread as embed_serve
from repro.obsv import teleserve, trace
from repro.obsv.metrics import (REGISTRY, Histogram, MetricsRegistry,
                                SampleWindow, log_bounds)
from repro.obsv.trace import NOOP_SPAN, TraceRecorder, merge_snapshots

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def _clean_trace():
    """Every test starts and ends with the global recorder disabled and
    empty (several suites share the process)."""
    trace.TRACE.disable()
    trace.TRACE.clear()
    trace.TRACE.context.clear()
    yield
    trace.TRACE.disable()
    trace.TRACE.clear()
    trace.TRACE.context.clear()


# -- histogram bucket math ----------------------------------------------------

def test_log_bounds_cover_range():
    b = log_bounds(1e-3, 1.0, 2.0)
    assert b[0] == 1e-3
    assert b[-1] >= 1.0
    for lo, hi in zip(b, b[1:]):
        assert hi == pytest.approx(lo * 2.0)


def test_histogram_bucket_placement_exact():
    h = Histogram("t", lo=1e-3, hi=1.0, factor=2.0)
    # a value equal to a bucket's upper bound lands IN that bucket
    h.observe(1e-3)
    assert h.counts[0] == 1
    h.observe(2e-3)
    assert h.counts[1] == 1
    # under lo → first bucket; over hi → +Inf overflow slot
    h.observe(1e-9)
    assert h.counts[0] == 2
    h.observe(50.0)
    assert h.counts[-1] == 1
    # count conservation + sidecars
    assert sum(h.counts) == h.count == 4
    assert h.vmin == 1e-9 and h.vmax == 50.0
    assert h.sum == pytest.approx(1e-3 + 2e-3 + 1e-9 + 50.0)
    assert h.mean == pytest.approx(h.sum / 4)


def test_histogram_quantile_monotone():
    h = Histogram("t", lo=1e-3, hi=10.0, factor=2.0)
    for v in np.geomspace(1e-3, 5.0, 200):
        h.observe(float(v))
    q50, q90, q99 = h.quantile(0.5), h.quantile(0.9), h.quantile(0.99)
    # estimates are bucket upper bounds: monotone, within the bound range
    assert q50 <= q90 <= q99 <= h.bounds[-1]
    assert q50 >= h.vmin


def test_registry_snapshot_delta_and_text():
    reg = MetricsRegistry()
    c = reg.counter("a.count")
    g = reg.gauge("a.level")
    h = reg.histogram("a.lat", lo=1e-3, hi=1.0, factor=2.0)
    c.inc(3)
    g.set(7.5)
    h.observe(0.25)
    before = reg.snapshot()
    c.inc(2)
    h.observe(0.5)
    g.set(1.0)
    delta = MetricsRegistry.delta(reg.snapshot(), before)
    assert delta["a.count"] == 2
    assert delta["a.lat"]["count"] == 1
    assert delta["a.lat"]["sum"] == pytest.approx(0.5)
    # scalar metrics subtract uniformly (a snapshot can't tell a gauge
    # from a counter; consumers pick the names they know are counters)
    assert delta["a.level"] == pytest.approx(1.0 - 7.5)
    text = reg.render_text()
    assert "a.count 5" in text
    assert 'a.lat_bucket{le="+Inf"} 2' in text
    assert "a.lat_count 2" in text
    # cumulative bucket lines are monotone non-decreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("a.lat_bucket")]
    assert cums == sorted(cums)


def test_registry_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_fn_backed_gauge_reads_live():
    reg = MetricsRegistry()
    box = {"v": 1}
    reg.gauge("live", fn=lambda: box["v"])
    assert reg.snapshot()["live"] == 1
    box["v"] = 9
    assert reg.snapshot()["live"] == 9


def test_kernel_compile_gauges_registered():
    import repro.kernels.quantize  # noqa: F401 — registers the gauges
    snap = REGISTRY.snapshot("kernels.")
    assert "kernels.quantize_padded.compiles" in snap
    assert snap["kernels.quantize_padded.compiles"] >= 0


# -- sample window (satellite: RpcSamples fold) -------------------------------

class _FakeSample:
    def __init__(self, op, measured_s, payload_bytes):
        self.op = op
        self.measured_s = measured_s
        self.payload_bytes = payload_bytes


def test_sample_window_feeds_histograms_once():
    reg = MetricsRegistry()
    w = SampleWindow("ex", maxlen=4, registry=reg)
    for i in range(6):
        w.observe(_FakeSample("gather", 1e-3 * (i + 1), 1024))
    # deque is bounded, histograms saw every observe
    assert len(w) == 4 and w.maxlen == 4
    snap = reg.snapshot()
    assert snap["ex.latency_s.gather"]["count"] == 6
    assert snap["ex.bytes.gather"]["count"] == 6
    w.clear()
    assert len(w) == 0
    # clearing the window must NOT rewind the histograms
    assert reg.snapshot()["ex.latency_s.gather"]["count"] == 6
    assert list(iter(w)) == []


# -- trace recorder -----------------------------------------------------------

def test_disabled_span_is_shared_noop_and_records_nothing():
    rec = TraceRecorder()
    assert rec.span("x") is NOOP_SPAN
    assert rec.span("y", args={"k": 1}) is NOOP_SPAN
    with rec.span("z"):
        pass
    assert len(rec.events) == 0


def test_enabled_span_records_name_tid_duration_args():
    rec = TraceRecorder()
    rec.enable()
    rec.set_context(round=3)
    with rec.span("outer", cat="phase", args={"client": 1}):
        with rec.span("inner"):
            pass
    assert len(rec.events) == 2
    names = [e[0] for e in rec.events]
    assert names == ["inner", "outer"]      # inner closes first
    for name, cat, tid, t0, dur, args in rec.events:
        assert tid == threading.get_ident()
        assert dur >= 0.0
        assert args["round"] == 3           # context tag merged
    outer = rec.events[1]
    assert outer[5] == {"round": 3, "client": 1}


def test_ring_buffer_bounded():
    rec = TraceRecorder(capacity=8)
    rec.enable()
    for i in range(100):
        with rec.span(f"s{i}"):
            pass
    assert len(rec.events) == 8
    assert rec.events[0][0] == "s92"        # oldest dropped


# -- spans of a federated round ---------------------------------------------

#: spans the trainer records around its untimed host work, and the
#: compile span from JAX's compile events
ROUND_HOST_SPANS = ("client.sample", "client.step_inputs",
                    "client.step_dispatch", "round.apply_push", "jit.compile")


@pytest.fixture(scope="module")
def traced_round():
    """A tiny OP trainer (int8 with error feedback): its first round
    traced (so it compiles inside the trace), then a second one with
    tracing off.  Returns both rounds' recorded events and the first
    round's wall seconds."""
    import dataclasses

    from repro.core import FederatedGNNTrainer, default_strategies
    from repro.graphs import make_graph
    g = make_graph("reddit", scale=0.05, seed=3)
    st = dataclasses.replace(default_strategies()["OP"], codec="int8",
                             error_feedback=True)
    tr = FederatedGNNTrainer(g, 2, st, batch_size=16, seed=0,
                             epochs_per_round=2)
    tr.pretrain_round()
    trace.TRACE.disable()
    trace.TRACE.clear()
    trace.TRACE.enable()
    t0 = time.perf_counter()
    tr.run_round(0, 0.0)
    wall = time.perf_counter() - t0
    trace.TRACE.disable()
    on = list(trace.TRACE.events)
    trace.TRACE.clear()
    tr.run_round(1, 0.0)
    off = list(trace.TRACE.events)
    trace.TRACE.context.clear()
    return {"on": on, "off": off, "wall": wall, "clients": tr.k,
            "epochs": tr.epochs}


@pytest.mark.parametrize("name", ROUND_HOST_SPANS)
def test_round_records_host_span(traced_round, name):
    got = [e for e in traced_round["on"] if e[0] == name]
    assert got, f"no {name} span in a traced round"
    for _, _, _, t0, dur, args in got:
        assert dur >= 0.0 and args["round"] == 0
    if name == "client.sample":
        assert sorted(e[5]["client"] for e in got) == \
            list(range(traced_round["clients"]))
    if name == "jit.compile":
        assert all(e[5]["fun"] for e in got)
    # the driver's own spans are parts of the round
    if name in ("client.sample", "round.apply_push"):
        assert sum(e[4] for e in got) <= traced_round["wall"]


def test_step_spans_are_parts_of_their_epoch(traced_round):
    events = traced_round["on"]
    epochs = [e for e in events if e[0] == "client.train_epoch"]
    assert len(epochs) == traced_round["clients"] * traced_round["epochs"]
    for _, _, tid, a, dur, args in epochs:
        parts = [e for e in events
                 if e[0] in ("client.step_inputs", "client.step_dispatch")
                 and e[2] == tid and a <= e[3] and e[3] + e[4] <= a + dur]
        kinds = {e[0] for e in parts}
        assert kinds == {"client.step_inputs", "client.step_dispatch"}, args
        assert sum(e[4] for e in parts) <= dur
        # the step spans carry no args of their own: only the context
        assert all(e[5] == {"round": 0} for e in parts)


def test_untraced_round_records_nothing(traced_round):
    assert traced_round["off"] == []
    assert trace.TRACE.span("client.step_inputs") is NOOP_SPAN
    assert trace.TRACE.span("client.sample", args={"client": 0}) \
        is NOOP_SPAN


@pytest.mark.parametrize("enabled", [True, False])
def test_compile_span_and_counter(enabled):
    import jax
    trace.install_jax_hooks()
    trace.install_jax_hooks()               # idempotent: one listener

    def fresh_kernel(x):
        return x * 3.0 + 1.0

    f = jax.jit(fresh_kernel)
    x = np.ones(5, np.float32)
    before = REGISTRY.snapshot("jit.")["jit.compiles"]
    if enabled:
        trace.TRACE.enable()
    jax.block_until_ready(f(x))
    got = [e for e in trace.TRACE.events if e[0] == "jit.compile"]
    assert REGISTRY.snapshot("jit.")["jit.compiles"] == before + 1
    if enabled:
        assert len(got) == 1 and "fresh_kernel" in got[0][5]["fun"]
        assert got[0][4] > 0.0
    else:
        assert got == []
    jax.block_until_ready(f(x))             # cached: neither moves
    assert REGISTRY.snapshot("jit.")["jit.compiles"] == before + 1
    assert [e for e in trace.TRACE.events if e[0] == "jit.compile"] == got


# -- chrome export + merge ----------------------------------------------------

def _sample_snapshot(label="p", n=3):
    rec = TraceRecorder(process=label)
    rec.enable()
    for i in range(n):
        with rec.span(f"e{i}", cat="test", args={"i": i}):
            pass
    return rec.snapshot()


def test_chrome_events_valid_schema():
    rec = TraceRecorder(process="me")
    rec.enable()
    with rec.span("work", args={"k": "v"}):
        pass
    events = rec.chrome_events()
    text = json.dumps({"traceEvents": events})
    parsed = json.loads(text)
    for ev in parsed["traceEvents"]:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], float) and isinstance(ev["dur"],
                                                              float)
            assert ev["dur"] >= 0.0


def test_merge_deterministic_and_distinct_pids():
    s1 = _sample_snapshot("alpha")
    s2 = _sample_snapshot("beta")
    doc_a = merge_snapshots([s1, s2], [0.0, 0.5])
    doc_b = merge_snapshots([s1, s2], [0.0, 0.5])
    assert json.dumps(doc_a, sort_keys=True) \
        == json.dumps(doc_b, sort_keys=True)
    meta = [e for e in doc_a["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in doc_a["traceEvents"] if e["ph"] == "X"]
    # both sources are threads of THIS process (same OS pid), but each
    # gets its own synthetic track
    assert len({e["pid"] for e in meta}) == 2
    assert {e["pid"] for e in spans} == {e["pid"] for e in meta}
    labels = {e["args"]["name"].split(" ")[0] for e in meta}
    assert labels == {"alpha", "beta"}


def test_merge_applies_clock_offsets():
    s1 = _sample_snapshot("a", n=1)
    s2 = json.loads(json.dumps(s1))
    s2["process"] = "b"
    base = merge_snapshots([s1], [0.0])
    shifted = merge_snapshots([s2], [10.0])
    t_base = [e["ts"] for e in base["traceEvents"] if e["ph"] == "X"][0]
    t_shift = [e["ts"] for e in shifted["traceEvents"]
               if e["ph"] == "X"][0]
    assert t_shift - t_base == pytest.approx(10.0 * 1e6, rel=1e-6)


# -- live TCP scrape: all server types ----------------------------------------

def test_scrape_embed_server_roundtrip():
    trace.TRACE.enable()
    with embed_serve(3, 8) as h:
        tr = TcpTransport(3, 8, [h.address])
        gids = np.arange(16)
        tr.register(gids)
        tr.write(gids, [np.random.default_rng(0).standard_normal(
            (16, 8)).astype(np.float32)] * 2)
        tr.gather(gids)
        with teleserve.TelemetryClient(h.address) as c:
            sc = c.scrape("embed0")
        tr.close()
    assert sc.pid > 0
    # same-process loopback: offsets are sub-50ms even on a loaded box
    assert abs(sc.offset_s) < 0.05
    # client-side RPC histograms and server-side spans both visible
    assert sc.metrics["exchange.latency_s.gather"]["count"] >= 1
    assert sc.metrics["exchange.bytes.write"]["count"] >= 1
    assert any(e[0].startswith("embed.") for e in sc.trace["events"])
    # sample window and histogram saw the same RPCs
    n_gather = sum(1 for s in tr.rpc_samples if s.op == "gather")
    assert sc.metrics["exchange.latency_s.gather"]["count"] >= n_gather


def test_scrape_coordinator_roundtrip():
    from repro.fedsvc.coordinator import CoordinatorState
    from repro.fedsvc.coordinator import serve_in_thread as coord_serve
    state = CoordinatorState(num_clients=1, num_rounds=1)
    h = coord_serve(state)
    try:
        with teleserve.TelemetryClient(h.address) as c:
            m, off_m = c.metrics()
            t, off_t = c.trace()
    finally:
        h.stop()
    assert "coord.aggregations" in m["metrics"]
    assert abs(off_m) < 0.05 and abs(off_t) < 0.05
    assert t["pid"] > 0 and isinstance(t["events"], list)


class _StubPlane:
    """pending()/stats() are all the frontend needs when no predict
    traffic flows — keeps the scrape test independent of a trained
    model."""

    def pending(self):
        return 0

    def step(self):
        return []

    def stats(self):
        return {"served": 0, "exits_by_depth": {}, "forwards": 0,
                "cache": {}, "cache_hit_rate": 0.0}


def test_scrape_gnnserve_frontend_and_registry_backed_sstats():
    from repro.gnnserve.frontend import GnnServeClient
    from repro.gnnserve.frontend import serve_in_thread as front_serve
    h = front_serve(_StubPlane())
    try:
        with teleserve.TelemetryClient(h.address) as c:
            sc = c.scrape("serve")
        cli = GnnServeClient(h.address)
        stats = cli.stats()
        cli.close()
    finally:
        h.stop()
    assert sc.pid > 0 and abs(sc.offset_s) < 0.05
    # satellite: OP_SSTATS carries the gnnserve.* registry slice next to
    # the plane's own counts, including the cache hit-rate
    assert "cache_hit_rate" in stats
    assert "metrics" in stats
    assert all(k.startswith("gnnserve.") for k in stats["metrics"])
    assert "gnnserve.cache.hits" in stats["metrics"]


def test_telemetry_only_listener_rejects_other_opcodes():
    with teleserve.serve_telemetry() as h:
        with teleserve.TelemetryClient(h.address) as c:
            sc = c.scrape("w0")
            assert sc.pid > 0
            # a data-plane opcode on the telemetry listener errors
            # cleanly instead of hanging the connection
            wire.send_frame(c._sock, wire.build_stats())
            resp = wire.recv_frame(c._sock)
            with pytest.raises(RuntimeError):
                wire.parse_response(resp)


def test_obs_dump_merges_multiple_endpoints(tmp_path):
    trace.TRACE.enable()
    with embed_serve(3, 8) as e1, embed_serve(3, 8) as e2, \
            teleserve.serve_telemetry() as w0:
        tr = TcpTransport(3, 8, [e1.address, e2.address])
        tr.register(np.arange(32))
        tr.close()
        doc, table = obs_dump.dump([
            ("embed0", e1.address), ("embed1", e2.address),
            ("worker0", w0.address)])
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(meta) == 3
    json.dumps(doc)                          # serializable end to end
    assert "# embed0" in table and "# worker0" in table
    assert "embed.requests" in table


def test_obs_dump_cli_writes_files(tmp_path):
    trace.TRACE.enable()
    with embed_serve(3, 8) as h:
        tr = TcpTransport(3, 8, [h.address])
        tr.register(np.arange(8))
        tr.close()
        out = tmp_path / "trace.json"
        mout = tmp_path / "metrics.txt"
        obs_dump.main(["--embed", f"{h.host}:{h.port}",
                       "--out", str(out), "--metrics-out", str(mout)])
    doc = json.loads(out.read_text())
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
    assert "embed.requests" in mout.read_text()


def test_servers_still_reject_unknown_opcodes():
    """Telemetry dispatch must not swallow genuinely bad opcodes."""
    with embed_serve(3, 8) as h:
        s = socket.create_connection(h.address)
        wire.send_frame(s, bytes([200]))
        resp = wire.recv_frame(s)
        s.close()
    with pytest.raises(RuntimeError, match="opcode"):
        wire.parse_response(resp)


# -- acceptance: 6 real processes, one obs_dump -------------------------------

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _scrapeable(endpoints) -> list | None:
    """One scrape attempt across all endpoints; None while any endpoint
    is still unreachable or span-less."""
    try:
        scrapes = teleserve.scrape_all(endpoints)
    except (ConnectionError, OSError, json.JSONDecodeError):
        return None
    if any(not s.trace.get("events") for s in scrapes):
        return None
    return scrapes


def _wait_listening(endpoints, timeout: float = 120.0) -> None:
    """Block until every endpoint answers a scrape: a client started
    before its server listens is refused and exits."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            teleserve.scrape_all(endpoints)
            return
        except (ConnectionError, OSError, json.JSONDecodeError):
            time.sleep(0.2)
    pytest.fail(f"servers never listened: {endpoints}")


@pytest.mark.slow
def test_six_process_obs_dump_acceptance(tmp_path):
    """Acceptance: coordinator + 2 workers + 2 embed shards + serving
    frontend as real OS processes under ``REPRO_TRACE=1``; one obs_dump
    invocation yields one valid Chrome trace with spans from all six
    processes plus the merged metrics table."""
    e1, e2, cp = _free_port(), _free_port(), _free_port()
    w0, w1, sp = _free_port(), _free_port(), _free_port()
    env = {**os.environ, "REPRO_TRACE": "1"}
    common = ["--graph", "reddit", "--scale", "0.05", "--graph-seed", "3",
              "--clients", "2", "--strategy", "E", "--rounds", "3",
              "--embed", f"127.0.0.1:{e1}", "--embed", f"127.0.0.1:{e2}"]
    endpoints = [("coordinator", f"127.0.0.1:{cp}"),
                 ("embed0", f"127.0.0.1:{e1}"),
                 ("embed1", f"127.0.0.1:{e2}"),
                 ("worker0", f"127.0.0.1:{w0}"),
                 ("worker1", f"127.0.0.1:{w1}"),
                 ("serve", f"127.0.0.1:{sp}")]
    procs = []
    try:
        for port in (e1, e2):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.launch.embed_server",
                 "--port", str(port), "--num-layers", "3",
                 "--hidden", "32"], env=env))
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.fed_coordinator",
             "--port", str(cp), "--timeout", "540"] + common,
            env=env, stdout=subprocess.DEVNULL))
        # serving frontend trains its model in-process (REPRO_TRACE=1 ⇒
        # the training spans are what its ring holds at scrape time)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.gnn_serve",
             "--port", str(sp), "--graph", "reddit", "--scale", "0.05",
             "--graph-seed", "3", "--clients", "2", "--strategy", "E",
             "--rounds", "1", "--cache-rows", "5000"],
            env=env, stdout=subprocess.DEVNULL))
        _wait_listening(endpoints[:3])    # coordinator and embed shards
        for i, wp in enumerate((w0, w1)):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.launch.fed_worker",
                 "--coordinator", f"127.0.0.1:{cp}",
                 "--client-ids", str(i), "--obs-port", str(wp),
                 "--straggler-s", "2.0"] + common,
                env=env, stdout=subprocess.DEVNULL))

        # poll until every process is up AND has recorded spans (the
        # straggler pacing keeps the workers alive long enough)
        deadline = time.monotonic() + 540
        while time.monotonic() < deadline:
            if _scrapeable(endpoints) is not None:
                break
            time.sleep(1.0)
        else:
            pytest.fail("deployment never became fully scrapeable")

        out = tmp_path / "trace.json"
        mout = tmp_path / "metrics.txt"
        obs_dump.main(["--coordinator", f"127.0.0.1:{cp}",
                       "--embed", f"127.0.0.1:{e1}",
                       "--embed", f"127.0.0.1:{e2}",
                       "--worker", f"127.0.0.1:{w0}",
                       "--worker", f"127.0.0.1:{w1}",
                       "--serve", f"127.0.0.1:{sp}",
                       "--out", str(out), "--metrics-out", str(mout)])
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()

    doc = json.loads(out.read_text())
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(meta) == 6
    # every one of the six tracks contributed at least one span
    assert {e["pid"] for e in spans} == {e["pid"] for e in meta}
    # real OS pids are distinct processes, not threads of the test
    real_pids = {e["args"]["name"].rsplit("pid ", 1)[1].rstrip(")")
                 for e in meta}
    assert len(real_pids) == 6
    assert os.getpid() not in {int(p) for p in real_pids}
    for ev in spans:
        assert ev["dur"] >= 0.0 and isinstance(ev["ts"], float)
    table = mout.read_text()
    for label in ("coordinator", "embed0", "worker1", "serve"):
        assert f"# {label}" in table
    assert "coord.aggregations" in table
    assert "embed.requests" in table
