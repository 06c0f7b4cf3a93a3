"""Smoke test of the system's main path on one TPU chip.

    python chip_smoke.py

One process, four phases, each a plain function:

  device  jax must have opened a TPU; on any other platform the script
          exits non-zero before doing any work.
  kernel  the int8 wire codec (``ops.quantize_int8`` /
          ``ops.dequantize_int8``, ``use_pallas="auto"``) on the chip at
          two row buckets and two hidden widths: the program is a Mosaic
          kernel, and its output is bit-identical to the numpy mirror.
  train   federated training through ``FederatedGNNTrainer`` on the
          reddit preset at scale 10 (40,000 vertices, 96 features, 41
          classes), 4 clients, strategy OP with the int8 codec and error
          feedback, 2 rounds; then OPP (scored prefetch, §4.3) for 2
          rounds at scale 2.  Losses must be finite and the final accuracy
          at least 0.9.
  serve   the OP model exported into the serving plane, 256 vertex
          queries at thresholds 1.0 and 0.5: every request id answered
          exactly once, threshold-1.0 answers equal to ``offline_predict``.

The last line of stdout is one JSON object naming the device; it is
printed only when every phase passed.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.launch.chip import (  # noqa: E402
    announce_device, enable_compile_cache)


class PhaseError(RuntimeError):
    """A phase's output failed its check."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseError(msg)


def device_phase() -> dict:
    """The devices jax opened; exits non-zero unless they are TPUs."""
    info = announce_device("device")
    if info["platform"] != "tpu":
        sys.exit(f"device phase: jax found no TPU (platform "
                 f"{info['platform']!r}); this smoke runs only on the chip")
    return info


def kernel_phase(rows=(300, 16384), hiddens=(32, 128), seed: int = 0
                 ) -> dict:
    """Int8 codec on the chip vs the numpy mirror, bit for bit."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, quantize

    probe = jax.ShapeDtypeStruct((quantize.ROW_TILE, quantize.LANE),
                                 jnp.float32)
    hlo = quantize.quantize_padded.lower(probe, interpret=False) \
        .compile().as_text()
    mosaic = "tpu_custom_call" in hlo
    print(f"kernel: quantize_padded compiled as tpu_custom_call: {mosaic}",
          flush=True)
    check(mosaic, "quantize_padded did not compile to a Mosaic kernel")

    rng = np.random.default_rng(seed)
    out = {}
    for n in rows:
        for h in hiddens:
            x = (rng.standard_normal((n, h))
                 * rng.uniform(0.01, 10.0, (n, 1))).astype(np.float32)
            x[0] = 0.0                     # an all-zero row: scale 0
            v, s = ops.quantize_int8(x)
            v, s = np.asarray(v), np.asarray(s)
            v_ref, s_ref = ops._np_quantize_int8(x)
            bad_v = int((v != v_ref).sum())
            d_scale = float(np.abs(s - s_ref).max())
            back = np.asarray(ops.dequantize_int8(v, s))
            bad_d = int((back != ops._np_dequantize_int8(v_ref, s_ref))
                        .sum())
            print(f"kernel: rows={n} hidden={h} int8_mismatches={bad_v} "
                  f"max_scale_diff={d_scale} dequant_mismatches={bad_d}",
                  flush=True)
            out[(n, h)] = (bad_v, d_scale, bad_d)
    compiles = quantize.quantize_padded._cache_size()
    print(f"kernel: quantize_padded programs compiled: {compiles}",
          flush=True)
    check(compiles > 0, "ops.quantize_int8 did not reach the Pallas kernel")
    check(all(r == (0, 0.0, 0) for r in out.values()),
          f"int8 codec differs from the numpy mirror: {out}")
    return out


def train_phase(strategy: str = "OP", *, scale: float = 10.0,
                clients: int = 4, rounds: int = 2, min_accuracy: float = 0.9,
                seed: int = 0):
    """Federated training on the reddit preset; returns the trainer."""
    from repro.core import FederatedGNNTrainer, default_strategies
    from repro.graphs import make_graph

    tag = f"train[{strategy} reddit scale={scale:g}]"
    st = dataclasses.replace(default_strategies()[strategy], codec="int8",
                             error_feedback=True)
    t0 = time.perf_counter()
    g = make_graph("reddit", scale=scale, seed=seed)
    tr = FederatedGNNTrainer(g, clients, st, seed=seed)
    print(f"{tag}: V={g.num_vertices} E={g.num_edges} "
          f"features={g.feat_dim} classes={g.num_classes} "
          f"clients={clients} ({st.describe()}); "
          f"setup_s={time.perf_counter() - t0:.3f}", flush=True)
    t0 = time.perf_counter()
    tr.pretrain_round()
    print(f"{tag}: pretrain_s={time.perf_counter() - t0:.3f}", flush=True)
    cum, stats = 0.0, []
    for r in range(rounds):
        t0 = time.perf_counter()
        s = tr.run_round(r, cum)
        wall = time.perf_counter() - t0
        cum = s.cum_time
        stats.append(s)
        print(f"{tag}: round {r} wall_s={wall:.3f} "
              f"round_time={s.round_time:.3f} (modelled network time "
              f"mixed in) accuracy={s.accuracy:.4f} "
              f"loss={s.train_loss:.4f}", flush=True)
    print(f"{tag}: train-step programs compiled: "
          f"{tr._train_step._cache_size()}", flush=True)
    check(all(math.isfinite(s.train_loss) for s in stats),
          f"{tag}: non-finite loss {[s.train_loss for s in stats]}")
    check(stats[-1].accuracy >= min_accuracy,
          f"{tag}: final accuracy {stats[-1].accuracy:.4f} < "
          f"{min_accuracy}")
    return tr


def serve_phase(tr, *, queries: int = 256, seed: int = 0) -> dict:
    """Serve the trained model; rid accounting + offline agreement."""
    from repro.gnnserve import build_serving

    t0 = time.perf_counter()
    plane = build_serving(tr.export_for_serving(), serve_fanout=10,
                          batch_size=64, depth_schedule=[1, 3])
    print(f"serve: exported + built in {time.perf_counter() - t0:.3f}s",
          flush=True)
    rng = np.random.default_rng(seed)
    vids = rng.integers(0, len(plane.part), size=queries)
    thresholds = np.where(np.arange(queries) % 2 == 0, 1.0, 0.5)
    sent = {plane.submit(int(v), float(t)): (int(v), float(t))
            for v, t in zip(vids, thresholds)}
    t0 = time.perf_counter()
    done = plane.drain()
    drain_s = time.perf_counter() - t0
    rids = collections.Counter(r.rid for r in done)
    once = sorted(rids) == sorted(sent) and max(rids.values()) == 1

    exact = {r.rid: r for r in done if sent[r.rid][1] == 1.0}
    by_owner = collections.defaultdict(list)
    for v in sorted({sent[rid][0] for rid in exact}):
        by_owner[int(plane.part[v])].append(v)
    ref = {}
    for ci, vs in by_owner.items():
        eng = plane.engines[ci]
        for i in range(0, len(vs), eng.batch_size):
            chunk = vs[i: i + eng.batch_size]
            lids = np.array([eng.local_id(v) for v in chunk], np.int64)
            ref.update(zip(chunk, eng.offline_predict(lids).tolist()))
    agree = sum(r.pred == ref[sent[rid][0]] for rid, r in exact.items())
    stats = plane.stats()
    print(f"serve: {len(sent)} submitted, {len(done)} answered, every id "
          f"once: {once}; threshold-1.0 equal to offline_predict: "
          f"{agree}/{len(exact)}; exits_by_depth={stats['exits_by_depth']} "
          f"drain_s={drain_s:.3f}", flush=True)
    check(once, "serving lost or duplicated request ids")
    check(agree == len(exact),
          "threshold-1.0 serving differs from offline_predict")
    return stats


def count_cache_events() -> collections.Counter:
    """Count persistent compile-cache hits and misses from here on."""
    import jax
    events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: events.update([event.rsplit("/", 1)[-1]]))
    return events


def main() -> None:
    enable_compile_cache()
    cache = count_cache_events()
    dev = device_phase()
    t0 = time.perf_counter()
    kernel_phase()
    tr = train_phase("OP", scale=10.0)
    train_phase("OPP", scale=2.0)
    serve_phase(tr)
    import jax
    print(f"compile cache {jax.config.jax_compilation_cache_dir}: "
          f"{cache['cache_hits']} hits, {cache['cache_misses']} misses",
          flush=True)
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
