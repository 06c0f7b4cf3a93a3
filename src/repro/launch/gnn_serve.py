"""CLI: GNN inference-serving frontend.

Trains a federated run from the shared RunConfig flags (so the served
model is pinned by the same argv contract as ``fedrun``), exports the
trained parameters + final-epoch boundary embeddings into the serving
plane (:meth:`FederatedGNNTrainer.export_for_serving`), and answers
``OP_PREDICT`` queries over TCP until an ``OP_EMBED_SHUTDOWN`` frame arrives.

    python -m repro.launch.gnn_serve --port 7060 \
        --graph reddit --scale 0.05 --graph-seed 3 \
        --clients 2 --strategy E --rounds 2 \
        --cache-rows 50000 --serve-fanout 10 --depth-schedule 1,2,3

Query it with :class:`repro.gnnserve.frontend.GnnServeClient` or the
open-loop bench (``benchmarks/bench_gnnserve.py``).
"""

from __future__ import annotations

import argparse
import json
import time

from repro.fedsvc.runtime import RunConfig
from repro.gnnserve import build_serving
from repro.gnnserve.frontend import serve_in_thread
from repro.launch.chip import announce_device, enable_compile_cache
from repro.obsv.trace import TRACE


def build_plane_from_cfg(cfg: RunConfig, *, cache_rows: int,
                         serve_fanout: int, batch_size: int,
                         depth_schedule=None, quiet: bool = False):
    """Train ``cfg.rounds`` rounds in-process, export, build the plane.
    Shared with the bench so CLI and bench serve the identical model."""
    trainer = cfg.build_trainer()
    trainer.pretrain_round()
    for rnd in range(cfg.rounds):
        stats = trainer.run_round(rnd, 0.0)
        if not quiet:
            print(f"round {rnd}: acc={stats.accuracy:.4f}", flush=True)
    bundle = trainer.export_for_serving()
    plane = build_serving(bundle, cache_rows=cache_rows,
                          serve_fanout=serve_fanout, batch_size=batch_size,
                          depth_schedule=depth_schedule)
    return trainer, plane


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        description="GNN node-prediction serving frontend")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 picks an ephemeral port (printed on start)")
    ap.add_argument("--cache-rows", type=int, default=100_000,
                    help="hot-embedding cache capacity (rows, LRU)")
    ap.add_argument("--serve-fanout", type=int, default=10,
                    help="deterministic per-hop neighbour cap at serve time")
    ap.add_argument("--serve-batch", type=int, default=64,
                    help="padded forward batch size of the query batcher")
    ap.add_argument("--depth-schedule", default=None,
                    help="comma-separated ascending early-exit depths "
                         "ending at num-layers (default 1,..,L)")
    RunConfig.add_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    announce_device("gnn_serve")

    cfg = RunConfig.from_args(args)
    sched = None
    if args.depth_schedule:
        sched = [int(d) for d in args.depth_schedule.split(",")]
    t0 = time.perf_counter()
    _trainer, plane = build_plane_from_cfg(
        cfg, cache_rows=args.cache_rows, serve_fanout=args.serve_fanout,
        batch_size=args.serve_batch, depth_schedule=sched)
    print(f"trained + exported in {time.perf_counter() - t0:.1f}s",
          flush=True)

    handle = serve_in_thread(plane, host=args.host, port=args.port)
    TRACE.set_process(f"gnn_serve:{handle.port}")
    print(f"gnn_serve listening on {handle.host}:{handle.port} "
          f"shards={sorted(plane.engines)} "
          f"schedule={next(iter(plane.engines.values())).depth_schedule}",
          flush=True)
    try:
        while not handle._state.stop.is_set():
            time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    finally:
        handle.stop()
        print(json.dumps(plane.stats()), flush=True)


if __name__ == "__main__":
    main()
