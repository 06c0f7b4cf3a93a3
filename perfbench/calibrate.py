"""Readings that set a training cell's limits for ``correct``.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--faults 3] [--out <file.jsonl>]

On the chip, at the cell's own size, for each seed: the set-up of a
run (one trainer, driven through its set-up round, which is recorded),
then the gaps of the program, of the control (the reference at
``high``, three bf16 passes, put in the program's place), and, on the
first ``--faults`` seeds, of each fault in :data:`FAULTS` planted in
the reference put in the program's place.  A step that returns its
state unchanged reads 1 on the gradient and change gaps by the measure
and needs no run.  No window runs.

Prints one JSON line per seed and reading, then the lower reading (the
program's largest gap over the seeds) and the upper readings (the
smallest control and fault gaps) of each compared number.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402


def _half_batch(stacked):
    """The mean taken over the first half of each step's seeds."""
    mask = stacked["mask"].copy()
    n = mask.sum(axis=1)
    cols = mask.cumsum(axis=1)
    mask[cols > ((n + 1) // 2)[:, None]] = 0.0
    return {**stacked, "mask": mask}


def _scaled(tables, by):
    return [t * by for t in tables]


def _one_client(models, weights):
    return models[0]


#: fault name -> the reference stage it replaces (``reference_side``'s
#: ``plant``)
FAULTS = {
    "half_batch": {"stacked": _half_batch},
    "no_exchange": {"tables": lambda t: _scaled(t, 0.0),
                    "server": lambda after, before: _scaled(after, 0.0)},
    "altered": {"tables": lambda t: _scaled(t, 1.05),
                "server": lambda after, before: _scaled(after, 1.05)},
    "push_skipped": {"server": lambda after, before: before},
    "ef_dropped": {"residual": lambda r: _scaled(r, 0.0)},
    "one_client_averaged": {"average": _one_client},
    "eval_stale": {"eval": lambda avg, start: start},
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--faults", type=int, default=3)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from perfbench import harness
    bench = harness.load_json(root / "BENCHMARK.json")
    files = harness.cell_files(bench, args.workload)
    harness.configure_jax()
    try:
        harness.device_info(int(files["cell"]["chips"]))
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, str(root / "src"))
    fr = harness.driver_of(files["traffic"])
    from perfbench.yardstick import compare
    cfg = files["config"]
    rows = []
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        tr, seen, graph, params0 = fr.build(cfg, seed)
        del tr
        gc.collect()
        t1 = time.perf_counter()
        ref = fr.reference_side(seen, cfg, graph, params0)
        t_ref = time.perf_counter() - t1
        readings = {"program": fr.gaps_of(fr.program_side(seen, cfg), ref,
                                          params0),
                    "control": fr.gaps_of(fr.reference_side(
                        seen, cfg, graph, params0, mode="high"),
                        ref, params0)}
        if i < args.faults:
            for fault, plant in FAULTS.items():
                readings[fault] = fr.gaps_of(fr.reference_side(
                    seen, cfg, graph, params0, plant=plant), ref, params0)
        for kind, gaps in readings.items():
            row = {"workload": args.workload, "seed": seed, "kind": kind,
                   **gaps}
            rows.append(row)
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                print(line, file=out, flush=True)
        print(f"calibrate: seed {seed} took {time.perf_counter() - t0:.1f}s"
              f", reference {t_ref:.1f}s", file=sys.stderr, flush=True)
    summary = {"workload": args.workload, "seeds": len(args.seeds)}
    for name in compare.NAMES:
        summary[name] = {
            "lower": max(r[name] for r in rows if r["kind"] == "program"),
            **{f"upper_{k}": min(r[name] for r in rows if r["kind"] == k)
               for k in ("control",) + tuple(FAULTS)
               if any(r["kind"] == k for r in rows)}}
    print(json.dumps(summary), flush=True)
    if out:
        print(json.dumps(summary), file=out, flush=True)
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
