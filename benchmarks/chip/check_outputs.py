#!/usr/bin/env python3
"""The readings that set a cell's limits: the program, the control and
the planted faults, each against the reference, at the cell's size.

    python3 benchmarks/chip/check_outputs.py --workload qwen05b-b8-stream \
        --seeds 11,12,13,14,15,16,17,18,19,20,21,22 --control-seeds 3

One job is built and round 0 runs once per seed through it (no
measured window: only the readings). For the first ``--control-seeds``
seeds, the reference computed in bfloat16 (the control) and each fault
of :data:`refmodel.FAULTS` are compared in the program's place. The
benchmark's own runs never run this. Prints one line per reading and,
last, a JSON summary: per number, the largest program reading (the
lower end of its limit) and the smallest control and fault readings.
Needs a TPU unless ``--cpu`` is given.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def readings(cell: harness.Cell, seeds: list[int], control_seeds: int,
             log=print) -> dict:
    import jax.numpy as jnp

    import refmodel

    job = harness.build(cell, seeds[0])
    out: dict = {"program": {}, "control": {}, "faults": {f: {} for f in refmodel.FAULTS}}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        r0 = harness.round0(job, cell, seed)
        t1 = time.perf_counter()
        args = (cell, seed, r0["prog"], r0["w0"])
        numbers, ref = harness.reference_numbers(*args)
        if i == 0:
            import outcheck

            counted = outcheck.counted_leaves(ref["grad_norms"])
            out["left_out"] = {k: g for k, g in ref["grad_norms"].items() if k not in counted}
            out["grad_norms"] = ref["grad_norms"]
            log(f"leaves left out of the change: {out['left_out']}")
        out["program"][seed] = numbers
        log(f"program seed {seed}: {json.dumps(numbers)} (program round {t1 - t:.3f} s, "
            f"reference {time.perf_counter() - t1:.3f} s)")
        if i >= control_seeds:
            continue
        numbers, _ = harness.reference_numbers(*args, dtype=jnp.bfloat16, ref=ref)
        out["control"][seed] = numbers
        log(f"control seed {seed}: {json.dumps(numbers)}")
        for fault in refmodel.FAULTS:
            numbers, _ = harness.reference_numbers(*args, fault=fault, ref=ref)
            out["faults"][fault][seed] = numbers
            log(f"fault {fault} seed {seed}: {json.dumps(numbers)}")
    del job
    names = sorted({k for v in out["program"].values() for k in v})
    summary = {}
    for name in names:
        summary[name] = {
            "program_max": max(v[name] for v in out["program"].values()),
            "control_min": min((v[name] for v in out["control"].values()), default=None),
            **{f"{f}_min": min((v[name] for v in out["faults"][f].values()), default=None)
               for f in refmodel.FAULTS},
        }
    out["summary"] = summary
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--cpu", action="store_true", help="allow a run without a TPU")
    args = ap.parse_args(argv)
    root = os.getcwd()
    cell = harness.Cell.load(root, args.workload)
    harness.import_program(root)
    import jax

    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("check_outputs: no TPU (pass --cpu to run here)", file=sys.stderr)
        return 2
    from repro.utils.jax_env import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = readings(cell, seeds, args.control_seeds)
    os.makedirs(harness.OUT, exist_ok=True)
    with open(os.path.join(harness.OUT, f"check-{args.workload}.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
