"""One run of one cell: set-up, the measured window, the check.

The rounds go through the program's normal path, ``build_job(spec)``
then ``Job.run`` (``FLSimulator.run`` -> ``ScatterAndGather``), with
two things taken from the benchmark rather than the program: the
round-0 weights (:func:`refmodel.init_weights`, from the seed) and each
client's token rows (:class:`cells.TokenSource`, from the seed).

Set-up builds the job, makes the weights, and runs round 0, which
compiles (or loads from the persistent cache) every program the cell
uses. The window then runs whole rounds back to back in the same
``Job.run`` call and closes at the first round boundary at or after
``seconds``. Round 0 is what ``correct`` compares with the reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import re
import resource
import sys
import time
import types
from typing import Any, Callable, Optional

import numpy as np

import cells

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(ROOT, "chiprun_out", "chipbench")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised from the round hook to end ``Job.run`` at a round boundary."""


def import_program(root: str = ROOT) -> None:
    src = os.path.join(root, "src")
    if not os.path.isfile(os.path.join(src, "repro", "fl", "job.py")):
        raise FileNotFoundError(f"no repro package under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def register_model(config: dict) -> str:
    """Make the program's config registry resolve ``config["arch_id"]``
    to the configuration in the file."""
    from repro.configs import base as registry
    from repro.models.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    # JSON has lists where the config has tuples (``block_pattern``); a
    # frozen config passed as a static argument must hash
    cfg = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                         for k, v in cells.model_fields(config, fields).items()})
    modname = "chipbench_" + re.sub(r"[^0-9A-Za-z]", "_", cfg.arch_id)
    mod = types.ModuleType(f"repro.configs.{modname}")
    mod.CONFIG = cfg
    mod.SMOKE_OVERRIDES = {}
    sys.modules[mod.__name__] = mod
    registry._MOD[cfg.arch_id] = modname
    return cfg.arch_id


def feed_tokens(job: Any, seed: int, traffic: dict, vocab: int) -> None:
    """Give each client's executor the benchmark's token rows: the
    ``data`` its train function reads is replaced in its closure."""
    for i, proxy in enumerate(job.sim.proxies):
        fn = proxy.executor.train_fn
        names = fn.__code__.co_freevars
        if "data" not in names:
            raise RuntimeError("the client executor's train function reads no "
                               "'data'; the benchmark cannot feed it tokens")
        fn.__closure__[names.index("data")].cell_contents = cells.TokenSource(
            seed, i, traffic["seq"], vocab)


def host_weights(config: dict, seed: int) -> dict[str, np.ndarray]:
    import jax

    import refmodel

    return {k: np.asarray(v) for k, v in
            jax.device_get(refmodel.init_weights(config, seed)).items()}


def check_leaves(w0: dict, program_w0: dict) -> None:
    mine = {k: tuple(v.shape) for k, v in w0.items()}
    theirs = {k: tuple(np.shape(v)) for k, v in program_w0.items()}
    if mine != theirs:
        raise RuntimeError(f"the configuration's leaves {mine} are not the "
                           f"program's {theirs}")


@dataclasses.dataclass
class Cell:
    workload: str
    cell: dict
    config: dict
    traffic: dict
    limits: dict

    @classmethod
    def load(cls, root: str, workload: str) -> "Cell":
        bench = cells.load_benchmark(root)
        cell = cells.find_cell(bench, workload)
        return cls(workload, cell, cells.load_config(root, bench, cell["config"]),
                   cells.load_traffic(cell["traffic"]), cells.load_limits(workload))

    def batch_of(self, seed: int) -> Callable[[int, int], np.ndarray]:
        t, v = self.traffic, self.config["vocab_size"]
        return lambda c, key: cells.token_rows(seed, c, key, t["batch"], t["seq"], v)


def build(cell: Cell, seed: int) -> Any:
    from repro.fl.job import build_job

    register_model(cell.config)
    job = build_job(cells.job_spec(cell.config, cell.traffic, seed))
    feed_tokens(job, seed, cell.traffic, cell.config["vocab_size"])
    return job


def round0(job: Any, cell: Cell, seed: int, after: Optional[Callable] = None) -> dict:
    """Hand the job the seed's weights and tokens and run its rounds;
    round 0 is summarised for the check. ``after(rnd)`` runs at every
    later round's end and may raise :class:`WindowClosed`."""
    import outcheck

    w0 = host_weights(cell.config, seed)
    check_leaves(w0, job.init_weights)
    job.init_weights = w0
    feed_tokens(job, seed, cell.traffic, cell.config["vocab_size"])
    clients = cell.traffic["clients"]
    idx = outcheck.sample_index(w0, seed)
    out: dict[str, Any] = {"w0": w0, "nonfinite": 0}
    start = len(job.history)
    ctl = job.sim.controller

    def hook(rnd: int, weights: dict, _results: list) -> None:
        losses = job.history[start + rnd * clients: start + (rnd + 1) * clients]
        if rnd == 0:
            out["prog"] = outcheck.round_summary(losses, weights, w0, idx)
            if after is None:
                raise WindowClosed
            after(0)
            return
        out["nonfinite"] += sum(1 for v in losses if not math.isfinite(v))
        after(rnd)

    ctl.num_rounds = 1 << 30
    ctl.on_round_end = hook
    try:
        job.run()
    except WindowClosed:
        pass
    finally:
        ctl.on_round_end = None
    return out


def reference_numbers(cell: Cell, seed: int, prog_summary: Optional[dict], w0: dict,
                      dtype: Any = None,
                      fault: Optional[str] = None,
                      ref: Optional[dict] = None) -> tuple[dict, dict]:
    """Run the reference round and compare; with ``dtype`` or ``fault``
    the control or the fault is compared in the program's place."""
    import jax.numpy as jnp

    import outcheck
    import refmodel

    idx = outcheck.sample_index(w0, seed)
    if ref is None:
        r = refmodel.reference_round(w0, cell.config, cell.traffic, cell.batch_of(seed))
        ref = outcheck.round_summary(r["losses"], r["weights"], w0, idx)
        ref["grad_norms"] = r["grad_norms"]
    if dtype is not None or fault is not None:
        r = refmodel.reference_round(w0, cell.config, cell.traffic, cell.batch_of(seed),
                                     dtype=dtype or jnp.float32, fault=fault)
        prog_summary = outcheck.round_summary(r["losses"], r["weights"], w0, idx)
    numbers = outcheck.compared(prog_summary, ref,
                                outcheck.counted_leaves(ref["grad_norms"]))
    return numbers, ref


def memory_peaks() -> tuple[Optional[int], int]:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use"), resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        root: str = ROOT) -> dict:
    """One run of a cell of ``root``'s BENCHMARK.json on the chip;
    returns the result object (the last stdout line)."""
    bench = cells.load_benchmark(root)
    cell = Cell.load(root, workload)
    import_program(root)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.cell["chips"]:
        raise NoChip(f"JAX reports {len(devs)} {devs[0].platform} device(s); the cell "
                     f"needs {cell.cell['chips']} TPU chip(s) (no CPU fallback)")
    peaks = cells.load_peaks(devs[0].device_kind)
    return run_cell(cell, seed, seconds, trace, t_start,
                    [m["name"] for m in cells.end_to_end_metrics(bench, workload)],
                    cells.per_layer_metrics(bench, workload), peaks)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             end_to_end: list[str], per_layer: list[dict], peaks: Optional[dict],
             log: Callable[[str], None] = _log, compile_cache: bool = True) -> dict:
    """Set-up, window and check of one cell (any platform)."""
    import jax

    workload = cell.workload
    devs = jax.devices()
    from repro.obs import trace as obs_trace
    from repro.utils.jax_env import enable_compile_cache

    from annotate import CompileMeter, KernelCounter, ProfiledTracer

    cache = None
    if compile_cache:
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = CompileMeter()
    meter.install()
    log(f"chipbench: {workload} seed {seed} on {len(devs)} x {devs[0].device_kind}; "
        f"compile cache {cache}")
    t = time.perf_counter()
    job = build(cell, seed)
    log(f"chipbench: build_job {time.perf_counter() - t:.3f} s")
    tracer = ProfiledTracer(capacity=1 << 20) if trace else None
    counter = KernelCounter()
    trace_dir = os.path.join(OUT, f"{workload}-{seed}")
    state: dict[str, Any] = {"rounds": 0}

    def after(rnd: int) -> None:
        now = time.perf_counter()
        if rnd == 0:
            log(f"chipbench: round 0 ended {now - t_start:.3f} s after start")
            state["mark0"] = meter.mark()
            if trace:
                counter.install()
                jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
            state["t0"] = time.perf_counter()
            return
        state["rounds"] = rnd
        if now - state["t0"] >= seconds:
            state["t1"] = now
            state["mark1"] = meter.mark()
            if trace:
                jax.profiler.stop_trace()
                counter.uninstall()
            raise WindowClosed

    with obs_trace.activate(tracer) if tracer else contextlib.nullcontext():
        out = round0(job, cell, seed, after)
    if "t1" not in state:
        raise RuntimeError("the window did not close at a round boundary")
    window_s = state["t1"] - state["t0"]
    rounds = state["rounds"]
    dev_peak, host_peak = memory_peaks()
    c0, h0 = state["mark0"]
    c1, h1 = state["mark1"]
    log(f"chipbench: window {rounds} round(s) in {window_s:.6f} s; set-up "
        f"{state['t0'] - t_start:.6f} s; compilations inside the window: "
        f"{c1 - c0 + h1 - h0} ({c1 - c0} backend compiles, {h1 - h0} cache loads)")
    clients = cell.traffic["clients"]
    result: dict[str, Any] = {
        "correct": False, "attempted": rounds * clients, "failed": 0,
        "metrics": {},
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs), "memory_peak_bytes": dev_peak},
    }
    e2e = {"round_s": (window_s / rounds, "s"), "setup_s": (state["t0"] - t_start, "s"),
           "host_peak_gb": (host_peak / 1e9, "GB")}
    if dev_peak is not None:
        e2e["device_peak_gb"] = (dev_peak / 1e9, "GB")
    spans = list(tracer._events) if tracer else []
    del job
    gc.collect()

    t = time.perf_counter()
    numbers, _ = reference_numbers(cell, seed, out["prog"], out["w0"])
    log(f"chipbench: reference round and comparison {time.perf_counter() - t:.3f} s")
    numbers["nonfinite"] += out["nonfinite"]
    import outcheck

    ok, checks = outcheck.verdict(numbers, cell.limits)
    if trace:
        t = time.perf_counter()
        summary = _reduce_trace(trace_dir, spans)
        log(f"chipbench: trace reduced in {time.perf_counter() - t:.3f} s")
        ctx = _reader_context(cell, summary, spans, counter, peaks, window_s)
        for m in per_layer:
            value = cells.metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        for name in end_to_end:
            if name in e2e:
                value, unit = e2e[name]
                result["metrics"][name] = {"value": value, "unit": unit}
    result["correct"] = ok
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def _profile_options() -> Any:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _reduce_trace(trace_dir: str, spans: list[dict]) -> dict:
    import glob

    import tracereduce

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    names = {e["name"] for e in spans if e.get("ph") == "X"}
    return tracereduce.reduce_xplane(paths[-1], names)


def _reader_context(cell: Cell, summary: dict, spans: list[dict], counter: Any,
                    peaks: Optional[dict], window_s: float) -> Any:
    import tracereduce
    from refmodel import model_ref

    events, rounds = tracereduce.window_events(spans, first_round=1)
    t = cell.traffic
    ref = model_ref(cell.config)
    return types.SimpleNamespace(
        cell=cell.cell, traffic=t, config=cell.config,
        trace=summary, spans=events, rounds=rounds, window_s=window_s,
        steps=rounds * t["clients"] * t["local_steps"],
        step_flops=ref.step_flops(cell.config, t["batch"], t["seq"]),
        kernel_elems=dict(counter.elems),
        peaks=peaks,
    )
