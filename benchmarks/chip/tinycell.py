"""A cell of the benchmark cut to a size a CPU test run can hold.

The configuration, traffic mix and limits are the cell's own files;
only the sizes that the configuration's reference module cuts
(``tiny(model)``) and the sequence are shrunk, so every layer and every
wire and fold path of the cell still runs.
"""
from __future__ import annotations

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import cells  # noqa: E402
import harness  # noqa: E402
from refmodel import model_ref  # noqa: E402

SEQ = 64


def tiny_cell(workload: str) -> harness.Cell:
    cell = harness.Cell.load(ROOT, workload)
    cut = model_ref(cell.config).tiny(cell.config)
    cell.config = {**cell.config, **cut, "arch_id": f"chipbench.tiny.{workload}"}
    cell.traffic = {**cell.traffic, "seq": SEQ}
    return cell


def run_tiny(workload: str, seed: int = 3, trace: bool = False,
             seconds: float = 0.2) -> dict:
    """One whole run of the tiny cell on whatever JAX finds (the look
    for a chip is skipped), with the cell's own metric lists."""
    harness.import_program()
    bench = cells.load_benchmark(ROOT)
    cell = tiny_cell(workload)
    return harness.run_cell(
        cell, seed, seconds, trace, time.perf_counter(),
        [m["name"] for m in cells.end_to_end_metrics(bench, workload)],
        cells.per_layer_metrics(bench, workload), None, log=lambda _m: None,
        compile_cache=False)
