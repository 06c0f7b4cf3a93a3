"""Chip benchmark of the federated GNN system: one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees the chips
the cell asks for.  Prints one JSON result as the last line of stdout;
exits non-zero, with no result, where JAX finds no TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from perfbench import harness
    try:
        out, rounds = harness.run_cell(args, T_START)
    except harness.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    harness.emit(out, rounds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
