"""CLI: one federated client-worker process.

Owns one or more clients of the deployment, rebuilds the identical
graph/partition/model from the shared RunConfig flags, trains its
clients' share of every round through
``FederatedGNNTrainer.client_round``, exchanges embeddings with the
embed shards (``--embed``, repeatable) and weights with the coordinator
(``--coordinator``).

    python -m repro.launch.fed_worker --coordinator 127.0.0.1:7050 \
        --client-ids 0 --graph reddit --scale 0.05 --graph-seed 3 \
        --clients 2 --strategy E --rounds 2 \
        --embed 127.0.0.1:7040 --embed 127.0.0.1:7041

Scenario injection: ``--pacing 2.0`` makes this worker a uniform 2×
straggler, ``--straggler-s`` adds a fixed per-round delay, and
``--dropout-prob`` gives it a per-round chance of dying mid-round —
all three are reflected in both the measured wall clock (real sleeps)
and the modelled round-time ledger it reports to the coordinator.

Churn: ``--drop-round N`` kills the worker deterministically mid-round
N (after its pull, before its update — the spot that stresses the
coordinator most); adding ``--rejoin`` makes it come back after
``--rejoin-delay-s`` seconds on a fresh connection, re-hello with the
same client ids, and catch up from the coordinator's current model —
the worker re-join path end to end.
"""

from __future__ import annotations

import argparse
import json

from repro.fedsvc.runtime import RunConfig
from repro.fedsvc.worker import FedWorker, WorkerScenario
from repro.launch.chip import announce_device, enable_compile_cache
from repro.obsv import teleserve
from repro.obsv.trace import TRACE


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        description="Federated client worker (repro.fedsvc protocol)")
    ap.add_argument("--coordinator", required=True, metavar="HOST:PORT")
    ap.add_argument("--client-ids", required=True,
                    help="comma-separated client indices this worker owns")
    ap.add_argument("--worker-id", default=None)
    ap.add_argument("--pacing", type=float, default=1.0)
    ap.add_argument("--straggler-s", type=float, default=0.0)
    ap.add_argument("--dropout-prob", type=float, default=0.0)
    ap.add_argument("--scenario-seed", type=int, default=0)
    ap.add_argument("--drop-round", type=int, default=None,
                    help="die deterministically mid-round N (once)")
    ap.add_argument("--rejoin", action="store_true",
                    help="reconnect + re-hello after a drop instead of "
                         "staying dead")
    ap.add_argument("--rejoin-delay-s", type=float, default=0.5)
    ap.add_argument("--obs-port", type=int, default=None,
                    help="run a telemetry-only listener on this port "
                         "(OP_METRICS/OP_TRACE) so obs_dump can scrape "
                         "this worker — workers are otherwise pure "
                         "clients with no port of their own")
    RunConfig.add_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    announce_device("fed_worker")

    cfg = RunConfig.from_args(args)
    client_ids = [int(c) for c in args.client_ids.split(",") if c != ""]
    scenario = WorkerScenario(pacing=args.pacing,
                              straggler_s=args.straggler_s,
                              dropout_prob=args.dropout_prob,
                              seed=args.scenario_seed,
                              drop_round=args.drop_round,
                              rejoin=args.rejoin,
                              rejoin_delay_s=args.rejoin_delay_s)
    worker = FedWorker(cfg, client_ids, args.coordinator,
                       worker_id=args.worker_id, scenario=scenario)
    TRACE.set_process(f"fed_worker:{worker.worker_id}")
    obs = None
    if args.obs_port is not None:
        obs = teleserve.serve_telemetry(port=args.obs_port)
        print(f"fed_worker telemetry on {obs.host}:{obs.port}",
              flush=True)
    print(f"fed_worker {worker.worker_id} clients={client_ids} "
          f"coordinator={args.coordinator}", flush=True)
    try:
        records = worker.run()
    finally:
        if obs is not None:
            obs.stop()
    for rec in records:
        print(json.dumps(rec), flush=True)
    status = "DROPPED" if worker.dropped else \
        "DISCONNECTED" if worker.disconnected else "DONE"
    rejoined = f" rejoins={worker.rejoins}" if worker.rejoins else ""
    print(f"fed_worker {worker.worker_id} {status}{rejoined}", flush=True)


if __name__ == "__main__":
    main()
