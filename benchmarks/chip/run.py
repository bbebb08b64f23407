#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload qwen05b-b8-stream --seed 7 \
        --seconds 10 --trace 0

Run from the root of a checkout of the repository, on a machine whose
JAX sees a TPU. The cell (``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix; see ``harness.py`` for what a run
does. The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(``--trace 1``: the per-layer metrics, and ``breakdown``), and last
``checks``: each number the correctness check compared, beside its
limit. The same numbers end standard error.

Exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not beside it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="Run one cell of the chip benchmark once.")
    ap.add_argument("--workload", required=True, help="a cell name from BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the weights and the token rows (non-negative)")
    ap.add_argument("--seconds", type=float, required=True,
                    help="the window closes at the first round boundary past this")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window and report the per-layer metrics")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    try:
        result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                             T_START, root=os.getcwd())
    except (harness.NoChip, FileNotFoundError, KeyError) as exc:
        print(f"chipbench: {exc}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
