"""Benchmark harness: one module per paper table/figure + kernel/system

extras. Prints ``name,us_per_call,derived`` CSV (harness contract).

    PYTHONPATH=src python -m benchmarks.run [--only table2,table3,...]
                                            [--smoke] [--json PATH]

``--smoke`` runs the fast CI subset; ``--json`` writes a machine-readable
``BENCH_*.json`` report (rows, per-suite timings, failures, and a
metrics-registry snapshot) for the nightly workflow artifact. A suite
that raises is reported on stderr and the process exits non-zero, so CI
actually fails on benchmark regressions instead of passing silently.
``--trace PATH`` additionally runs one traced 2-round smoke federation
and writes its dual-clock Chrome trace-event file (open in Perfetto);
the nightly job uploads it next to the bench JSON.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
import traceback

SUITES: dict[str, str] = {
    "table2": "benchmarks.table2_message_size",
    "table3": "benchmarks.table3_streaming_memory",
    "fig45": "benchmarks.fig45_convergence",
    "kernels": "benchmarks.quant_kernels",
    "chunks": "benchmarks.streaming_chunks",
    "sensitivity": "benchmarks.layer_sensitivity",
    "roofline": "benchmarks.roofline_report",
    "async": "benchmarks.async_throughput",
    "hetero": "benchmarks.hetero_fleet",
    "envelope": "benchmarks.pipeline_envelope",
    "agg_memory": "benchmarks.agg_memory",
    "wire": "benchmarks.wire_throughput",
    "lora": "benchmarks.lora_wire",
    "live": "benchmarks.live_federation",
    "overlap": "benchmarks.overlap_throughput",
}

# fast subset for the nightly smoke run (skips the convergence sweeps);
# "envelope" keeps the wire pipeline's O(largest item) peak-memory claim
# under regression watch in BENCH_*.json, "agg_memory" does the same for
# the streaming aggregation plane's O(item) server peak, and "wire"
# carries the zero-copy plane's items/s rows that the nightly job diffs
# against the committed BENCH_5.json baseline (benchmarks/compare.py);
# "live" drives the real multi-process federation plane (TCP server +
# protocol-speaking clients) whose deterministic ordered-fold peaks diff
# against BENCH_7.json, "lora" pins the parameter-efficient uplink
# (bytes-vs-rank + streaming low-rank fold peak) against BENCH_8.json,
# and "overlap" pins the encode-ahead send path (depth>=1 must keep
# beating the sequential depth-0 loop on a paced link) against
# BENCH_9.json
SMOKE_SUITES = ("table2", "table3", "kernels", "chunks", "async", "hetero",
                "envelope", "agg_memory", "wire", "lora", "live", "overlap")


def _metrics_snapshot(timings: dict[str, float]) -> dict:
    """Harness-level metrics in the registry snapshot schema: per-suite
    elapsed gauges plus host peak RSS — embedded in the JSON report so
    the nightly artifact carries one uniform metrics shape."""
    from repro.obs import MetricsRegistry
    from repro.utils.mem import rss_peak_kb

    reg = MetricsRegistry()
    for name, secs in timings.items():
        reg.gauge("bench.suite_elapsed_s", suite=name).set(secs)
    rss = rss_peak_kb()
    if rss is not None:
        reg.gauge("bench.rss_peak_kb").set(rss)
    return reg.snapshot()


def _write_smoke_trace(path: str) -> dict:
    """One traced 2-round async smoke federation -> Chrome trace file.

    Exercises every instrumented layer at once: quantize+crc32 uplink
    stages, streaming server-side aggregation, the heterogeneous network
    model, and the event scheduler — so the artifact shows both clocks
    (wall spans per thread, simulated round anatomy per client)."""
    from repro.fl.job import run_job
    from repro.obs import validate_chrome_trace

    result = run_job({
        "arch": "llama3.2-1b",
        "rounds": 2,
        "clients": 2,
        "local_steps": 1,
        "pipeline": {"task_result_out": ["quantize:nf4", "crc32"]},
        "server_streaming_agg": True,
        "runtime": {"policy": "sync",
                    "network": {"kind": "hetero", "tiers": ["fiber", "lte"]}},
        "trace": path,
    })
    with open(path) as fh:
        validate_chrome_trace(json.load(fh))
    summary = dict(result["trace"])
    summary["telemetry"] = result["telemetry"]
    return summary


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated suite names")
    ap.add_argument("--smoke", action="store_true",
                    help=f"fast subset: {','.join(SMOKE_SUITES)}")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a JSON report (default BENCH_smoke.json with --smoke)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run a traced 2-round smoke federation and write its "
                         "Chrome trace-event JSON here (open in Perfetto)")
    args = ap.parse_args(argv)
    from repro.utils.jax_env import enable_compile_cache

    enable_compile_cache()

    if args.only:
        unknown = set(args.only.split(",")) - set(SUITES)
        if unknown:
            ap.error(f"unknown suites: {sorted(unknown)} (have {sorted(SUITES)})")
        selected = [s for s in SUITES if s in set(args.only.split(","))]
    elif args.smoke:
        selected = list(SMOKE_SUITES)
    else:
        selected = list(SUITES)
    json_path = args.json or ("BENCH_smoke.json" if args.smoke else None)

    print("name,us_per_call,derived")
    rows: list[str] = []
    timings: dict[str, float] = {}
    failures: dict[str, str] = {}
    t0 = time.time()
    for name in selected:
        t_suite = time.time()
        try:
            mod = importlib.import_module(SUITES[name])
            for row in mod.run():
                print(row)
                rows.append(row)
        except Exception as exc:  # noqa: BLE001 — a failed suite must not hide the rest
            traceback.print_exc()
            failures[name] = f"{type(exc).__name__}: {exc}"
        timings[name] = round(time.time() - t_suite, 3)
    elapsed = time.time() - t0
    print(f"# total {elapsed:.1f}s", file=sys.stderr)

    trace_summary = None
    if args.trace:
        try:
            trace_summary = _write_smoke_trace(args.trace)
            print(f"# wrote {args.trace}", file=sys.stderr)
        except Exception as exc:  # noqa: BLE001 — same isolation as suites
            traceback.print_exc()
            failures["trace"] = f"{type(exc).__name__}: {exc}"

    if json_path:
        report = {
            "suites": selected,
            "rows": rows,
            "timings_s": timings,
            "failures": failures,
            "elapsed_s": round(elapsed, 3),
            "metrics": _metrics_snapshot(timings),
        }
        if trace_summary is not None:
            report["trace"] = trace_summary
        with open(json_path, "w") as fh:
            json.dump(report, fh, indent=1)
        print(f"# wrote {json_path}", file=sys.stderr)

    if failures:
        for name, err in failures.items():
            print(f"# FAILED {name}: {err}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
