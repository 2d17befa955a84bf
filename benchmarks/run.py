"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It names no cell, configuration or metric; it finds them in BENCHMARK.json
and in the files that file points to (see benchmarks/README.md)."""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    from benchmarks.harness.cell import run_cell
    from benchmarks.harness.serving_system import persistent_compile_cache

    persistent_compile_cache()

    run_cell(ROOT, args.workload, args.seed, args.seconds,
             bool(args.trace), t_process=T_PROCESS)
    sys.stdout.flush()
    sys.stderr.flush()
    # leave without waiting for daemon threads or the runtime's teardown
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
