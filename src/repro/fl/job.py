"""Declarative FL job system (NVFlare-style): one JSON/dict describes the

whole federation — model, clients, data partitioning, the filter stack at
each of the four points, transmission mode, and the runtime scenario —
and the runner builds and executes it. The paper's "no code change, just
a configuration change" claim is this surface: switching quantization
on/off/format, streaming mode, or the *entire scheduling regime* touches
only the job spec.

    spec = {
      "arch": "llama3.2-1b", "smoke": true,
      "rounds": 5, "local_steps": 4, "batch": 8, "seq": 64, "lr": 3e-3,
      "clients": 3, "partition": "dirichlet", "alpha": 0.5,
      "pipeline": {                      # per-direction wire stacks, by name
        "task_data_out": ["quantize:nf4", "zlib"],
        "task_result_out": ["quantize:nf4", "zlib", "crc32"]
      },
      "transmission": "container", "driver": "loopback", "chunk_mb": 1,
      "server_streaming_agg": true,   # fold uplink items as they decode
      "aggregator": "fedavg",         # any registered aggregator name
      "runtime": {                       # optional: async scenario engine
        "policy": "fedasync",            # any registered policy name
        "max_concurrency": 8, "dropout_prob": 0.1, "max_retries": 2,
        "total_tasks": 15,               # fedasync/fedbuff task budget
        "network": {"kind": "hetero", "tiers": ["fiber", "lte", "3g"]},
        "availability": {"kind": "random", "mean_online_s": 60,
                         "mean_offline_s": 20, "horizon_s": 600}
      }
    }
    result = run_job(spec)

``"pipeline"`` entries are registered stage specs
(:mod:`repro.core.pipeline`): strings like ``"quantize:nf4"`` /
``"zlib:9"`` or dicts like ``{"stage": "adaptive", "budget_s": 0.5}``;
stage transforms run per item inside the streaming loop, so a
container-streamed quantized+compressed hop peaks at ~one item of
transmission memory. Policy names resolve through the runtime's policy
registry, driver names through the streaming driver registry — third-
party stages/drivers/policies plug in by registering, no job.py edits.

The older ``"quantization"``/``"dp_sigma"`` keys still work and build
the legacy Filter chains (adapted through the deprecated whole-message
shim); they are mutually exclusive with ``"pipeline"``. With
``{"fmt": "adaptive"}`` (or an ``"adaptive"`` pipeline stage) and a
runtime network, each client's wire precision tracks its simulated link
(slow links get 8-bit/NF4, fast links fp16/fp32) — see
``result["adaptive_fmts"]``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.filters import (
    AdaptiveQuantizeFilter,
    DequantizeFilter,
    DPGaussianNoiseFilter,
    ErrorFeedbackQuantizeFilter,
    FilterChain,
    FilterPoint,
    QuantizeFilter,
    no_filters,
)
from repro.core.pipeline import AdaptiveQuantizeStage, build_pipeline
from repro.data import dirichlet_partition, iid_partition
from repro.fl.aggregator import aggregator_consumes_wire, build_aggregator
from repro.kernels import ops
from repro.fl.executor import TrainExecutor
from repro.fl.simulator import FLSimulator, SimulationConfig
from repro.models import create_model
from repro.obs import trace as obs_trace
from repro.optim import adamw_init, adamw_update
from repro.utils.jax_env import enable_compile_cache
from repro.utils.trees import flatten_state_dict, unflatten_state_dict

DEFAULTS: dict[str, Any] = {
    "smoke": True,
    "rounds": 5,
    "local_steps": 4,
    "batch": 8,
    "seq": 64,
    "lr": 3e-3,
    "clients": 3,
    "partition": "iid",
    "alpha": 0.5,
    "quantization": None,
    "dp_sigma": 0.0,
    "pipeline": None,
    "transmission": "container",
    "driver": "loopback",
    "chunk_mb": 1,
    "server_quantized_aggregation": False,
    # streaming-first aggregation plane: Task Result items fold into the
    # aggregator one at a time inside the receive loop (server peak
    # transmission+aggregation memory ~ one item, not one model per
    # in-flight client); composes with every policy and with
    # server_quantized_aggregation
    "server_streaming_agg": False,
    # registry-keyed aggregator selection ("fedavg", "quantized-fedavg",
    # or anything registered via repro.fl.aggregator.register_aggregator);
    # None resolves from server_quantized_aggregation
    "aggregator": None,
    "runtime": None,
    # quantize-kernel backend for the whole run ("ref", "pallas",
    # "pallas_interpret", "auto"); None keeps the process default
    # (REPRO_KERNEL_BACKEND env, else auto). All backends produce
    # bitwise-identical payloads — this selects an implementation, never
    # a format — so it is a pure performance knob, declarative like
    # everything else here. The live federation plane passes it through
    # to the server and every client subprocess.
    "kernel_backend": None,
    # observability: truthy turns on the span tracer (flight recorder);
    # a string is also the Chrome-trace output path the run writes
    # (viewable in Perfetto / chrome://tracing). result["telemetry"]
    # carries the metrics snapshot either way.
    "trace": None,
    # fault tolerance (live plane only; the simulator ignores these).
    # quorum: fraction of the roster whose uplinks complete a round —
    # once reached and straggler_grace_s expires, the server folds the
    # contributors it has and re-invites stragglers next round. None
    # keeps the all-clients-or-round_timeout_s behavior.
    "quorum": None,
    "straggler_grace_s": 30.0,
    # reconnect budget per client process: transient ConnectionError /
    # timeout triggers capped exponential backoff + jitter, up to this
    # many attempts per run
    "max_reconnects": 5,
    # checkpoint: directory for atomic per-round server state (epoch +
    # global weights + roster) — the --resume restart point
    "checkpoint": None,
    # chaos: {client_name: fault plan} routed through a ChaosProxy per
    # afflicted client when spawning subprocesses (test/CI harness)
    "chaos": None,
    "seed": 0,
}


def normalize_spec(spec: dict[str, Any]) -> dict[str, Any]:
    """The canonical spec every builder consumes: ``DEFAULTS`` applied.

    Shared by :func:`build_job` and the live federation plane
    (:mod:`repro.launch.federation`) so both resolve identical settings
    from the same declarative input."""
    out = {**DEFAULTS, **spec}
    kb = out.get("kernel_backend")
    if kb is not None and kb not in ops.BACKENDS:
        raise ValueError(
            f'"kernel_backend" must be one of {ops.BACKENDS}, got {kb!r}'
        )
    return out


def kernel_backend_scope(spec: dict[str, Any]) -> Any:
    """Scoped application of the spec's ``"kernel_backend"`` selection —
    a :func:`repro.kernels.ops.backend` context when the key is set, a
    no-op otherwise. Shared by :meth:`Job.run` and the live federation
    plane (server run loop and client subprocess main), so one spec key
    selects the kernel implementation on every process of a deployment."""
    kb = spec.get("kernel_backend")
    return ops.backend(kb) if kb else contextlib.nullcontext()


def _adaptive_filter(q: dict[str, Any], network: Optional[Any]) -> AdaptiveQuantizeFilter:
    f = AdaptiveQuantizeFilter(
        bandwidth_bps=float(q.get("bandwidth_mbps", 80.0)) * 1e6,  # wifi-class fallback
        budget_s=float(q.get("budget_s", 1.0)),
        min_params=int(q.get("min_params", 0)),
    )
    if network is not None:
        f.bind_network(network)
    return f


_PIPELINE_DIRECTIONS = {
    # canonical hop names + legacy four-point OUT aliases
    "task_data": "task_data",
    "task_data_out": "task_data",
    "task_result": "task_result",
    "task_result_out": "task_result",
}


def _build_pipelines(spec: dict[str, Any], network: Optional[Any]):
    """Translate the ``"pipeline"`` spec block into FLSimulator pipelines.

    Returns (pipelines dict, adaptive stages found) — adaptive stages get
    the runtime network bound so per-client precision tracks the
    simulated link, and are reported in ``result["adaptive_fmts"]``.
    """
    p = spec["pipeline"]
    if spec.get("quantization") or spec.get("dp_sigma"):
        raise ValueError(
            '"pipeline" replaces the legacy "quantization"/"dp_sigma" keys; '
            'declare those transforms as stages (e.g. "quantize:nf4", '
            '{"stage": "dp-noise", "sigma": 0.01})'
        )
    unknown = set(p) - set(_PIPELINE_DIRECTIONS)
    if unknown:
        raise ValueError(
            f"unknown pipeline directions {sorted(unknown)}; "
            f"valid: {sorted(_PIPELINE_DIRECTIONS)}"
        )
    specs: dict[str, list[Any]] = {"task_data": [], "task_result": []}
    for key, stages in p.items():
        specs[_PIPELINE_DIRECTIONS[key]] += list(stages or [])
    # aggregators that fold wire-form payloads (QuantizedTensor /
    # LowRankDelta) need the uplink left undecoded
    keep_wire = bool(spec.get("server_quantized_aggregation")) or \
        aggregator_consumes_wire(aggregator_spec(spec))
    pipelines = {
        "task_data": build_pipeline(specs["task_data"]),
        "task_result": build_pipeline(specs["task_result"], decode_values=not keep_wire),
    }
    adaptive: list[AdaptiveQuantizeStage] = []
    for pl in pipelines.values():
        for stage in pl.stages:
            if isinstance(stage, AdaptiveQuantizeStage):
                if keep_wire:
                    raise ValueError(
                        "server_quantized_aggregation does not compose with the "
                        "adaptive stage: clients may ship mixed formats"
                    )
                if network is not None:
                    stage.bind_network(network)
                adaptive.append(stage)
    return pipelines, adaptive


def build_pipelines_from_spec(
    spec: dict[str, Any], network: Optional[Any] = None
) -> dict[str, Any]:
    """Wire pipelines for a job spec — the single construction path both
    federation planes share, so the server and every client subprocess of
    a live deployment provably run the same stage stacks the simulator
    would (the pipeline fingerprint in the live handshake hashes these).

    Specs without a ``"pipeline"`` block get identity pipelines (same
    wire container, no transforms). The legacy ``"quantization"`` /
    ``"dp_sigma"`` filter keys have no pipeline form and are rejected.
    """
    spec = normalize_spec(spec)
    if spec.get("pipeline"):
        pipelines, _ = _build_pipelines(spec, network)
        return pipelines
    if spec.get("quantization") or spec.get("dp_sigma"):
        raise ValueError(
            'the legacy "quantization"/"dp_sigma" keys build whole-message '
            'Filter chains with no streaming-pipeline form; declare them as '
            '"pipeline" stages (e.g. "quantize:nf4", '
            '{"stage": "dp-noise", "sigma": 0.01})'
        )
    keep_wire = bool(spec.get("server_quantized_aggregation")) or \
        aggregator_consumes_wire(aggregator_spec(spec))
    return {
        "task_data": build_pipeline([]),
        "task_result": build_pipeline([], decode_values=not keep_wire),
    }


def aggregator_spec(spec: dict[str, Any]) -> Any:
    """Resolve the spec's aggregator selection (registry key or config
    dict) exactly as :func:`build_job` does — shared with the live plane
    so a real server folds with the same aggregator the simulator would."""
    spec = normalize_spec(spec)
    agg = spec.get("aggregator")
    if agg is None:
        agg = (
            "quantized-fedavg"
            if spec.get("server_quantized_aggregation")
            and (spec.get("quantization") or spec.get("pipeline"))
            else "fedavg"
        )
    return agg


def _client_datasets(spec: dict[str, Any], cfg: Any) -> list[Any]:
    """Deterministic per-client datasets: seed-keyed partition, so every
    process that evaluates this (simulator or client subprocess) derives
    the identical per-client data streams."""
    if spec["partition"] == "dirichlet":
        return dirichlet_partition(
            cfg.vocab_size, spec["seq"], spec["clients"],
            alpha=spec["alpha"], seed=spec["seed"],
        )
    return iid_partition(
        cfg.vocab_size, spec["seq"], spec["clients"], seed=spec["seed"]
    )


def _jit_local_step(model: Any, lr: float):
    # params and optimizer state are donated: train_fn rebinds both every
    # step, and without aliasing a full-width step holds two copies of
    # weights + AdamW moments (16.1 GB for qwen1.5-0.5b at 4x512, more
    # than a 16 GB TPU v5e has)
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def local_step(params, opt, batch):
        (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
        params, opt, _ = adamw_update(params, grads, opt, jnp.float32(lr))
        return params, opt, loss

    return local_step


def _train_executor(
    name: str, data: Any, spec: dict[str, Any], local_step: Any,
    history: Optional[list[float]] = None,
) -> TrainExecutor:
    def train_fn(flat_params, rnd):
        with obs_trace.span("client.train", "client", client=name, round=rnd,
                            steps=spec["local_steps"]):
            # the task payload is consumed: device arrays the downlink decode
            # produced become the step's parameters without a copy, and the
            # first (donating) step frees them — a full-width client does not
            # hold the received weights beside its training state
            p = unflatten_state_dict(
                {k: jnp.asarray(v) for k, v in flat_params.items()}
            )
            opt = adamw_init(p)
            loss = None
            # round-keyed sampling makes the update a pure function of
            # (params, rnd): a client that reconnects or re-executes a round
            # after a fault regenerates the identical batches, so chaos and
            # resume runs stay bitwise-equal to clean ones
            for step in range(spec["local_steps"]):
                batch = {
                    k: jnp.asarray(v)
                    for k, v in data.sample_at(
                        spec["batch"], rnd * spec["local_steps"] + step
                    ).items()
                }
                p, opt, loss = local_step(p, opt, batch)
            if history is not None:
                history.append(float(loss))
            return flatten_state_dict(p), spec["batch"] * spec["local_steps"], {"loss": float(loss)}

    return TrainExecutor(name, train_fn)


def build_client_executor(
    spec: dict[str, Any], index: int, history: Optional[list[float]] = None
) -> TrainExecutor:
    """The executor for client ``index`` exactly as the simulator builds
    it — same model init path, same jitted local step, same seed-keyed
    data partition slice. The live federation plane's client subprocess
    entrypoint: bitwise sim-vs-real weight equality rests on this being
    one construction path, not two that happen to agree."""
    spec = normalize_spec(spec)
    cfg = get_smoke_config(spec["arch"]) if spec["smoke"] else get_config(spec["arch"])
    model = create_model(cfg)
    datasets = _client_datasets(spec, cfg)
    if not 0 <= index < len(datasets):
        raise ValueError(f"client index {index} out of range for {len(datasets)} clients")
    return _train_executor(
        f"site-{index}", datasets[index], spec, _jit_local_step(model, spec["lr"]), history
    )


def initial_weights(spec: dict[str, Any]) -> dict[str, Any]:
    """Round-0 global weights for a spec (flat state dict) — the shared
    starting point the live server downlinks, identical to what
    :func:`build_job` hands the simulator."""
    spec = normalize_spec(spec)
    cfg = get_smoke_config(spec["arch"]) if spec["smoke"] else get_config(spec["arch"])
    return _host_init(create_model(cfg), spec["seed"])


def _host_init(model: Any, seed: int) -> dict[str, np.ndarray]:
    """Seeded round-0 weights as a flat dict of host arrays: the server
    keeps global weights on the host in every later round (aggregators
    return NumPy), so round 0 does too, and the device stays free for
    the clients' training state."""
    flat = flatten_state_dict(model.init(jax.random.PRNGKey(seed)))
    return {k: np.asarray(v) for k, v in flat.items()}


def _build_filters(spec: dict[str, Any], network: Optional[Any] = None):
    """Two-way scheme (+optional EF / DP / link-adaptive) from the job spec."""
    server = no_filters()
    client = no_filters()
    adaptive: list[AdaptiveQuantizeFilter] = []
    q = spec.get("quantization")
    if q:
        fmt = q["fmt"]
        if fmt == "adaptive":
            if q.get("error_feedback"):
                raise ValueError("error_feedback does not compose with adaptive precision")
            if spec.get("server_quantized_aggregation"):
                # per-client formats can differ (that's the point), and the
                # fused aggregator needs one uniform wire format
                raise ValueError(
                    "server_quantized_aggregation does not compose with adaptive "
                    "precision: clients may ship mixed formats"
                )

            def mk():
                adaptive.append(_adaptive_filter(q, network))
                return adaptive[-1]
        elif q.get("error_feedback"):
            def mk():
                return ErrorFeedbackQuantizeFilter(fmt)
        else:
            def mk():
                return QuantizeFilter(fmt)
        server[FilterPoint.TASK_DATA_OUT] = FilterChain([mk()])
        client[FilterPoint.TASK_DATA_IN] = FilterChain([DequantizeFilter()])
        out_chain: list[Any] = []
        if spec.get("dp_sigma"):
            out_chain.append(DPGaussianNoiseFilter(spec["dp_sigma"], seed=spec["seed"]))
        out_chain.append(mk())
        client[FilterPoint.TASK_RESULT_OUT] = FilterChain(out_chain)
        if not spec.get("server_quantized_aggregation"):
            server[FilterPoint.TASK_RESULT_IN] = FilterChain([DequantizeFilter()])
    elif spec.get("dp_sigma"):
        client[FilterPoint.TASK_RESULT_OUT] = FilterChain(
            [DPGaussianNoiseFilter(spec["dp_sigma"], seed=spec["seed"])]
        )
    return server, client, adaptive


def _build_runtime(
    spec: dict[str, Any], aggregator: Any, client_names: list[str]
) -> dict[str, Any]:
    """Translate the ``"runtime"`` spec block into FLSimulator kwargs."""
    r = spec.get("runtime")
    if not r:
        return {}
    # imported lazily, same circularity constraint as fl.simulator
    from repro.runtime import (
        RuntimeConfig,
        availability_from_spec,
        network_from_spec,
        polynomial_staleness,
    )
    from repro.runtime.async_agg import build_policy

    r = dict(r)
    policy_name = r.get("policy", "sync")
    if policy_name in ("fedbuff", "fedasync") and spec.get("server_quantized_aggregation"):
        # these policies aggregate deltas/weights directly (not through the
        # aggregator) and skip QuantizedTensor payload items — quantized
        # server ingress would silently aggregate nothing
        raise ValueError(
            f"server_quantized_aggregation is not supported with policy "
            f"{policy_name!r}; it requires the aggregator path (sync/tiered)"
        )
    seed = int(r.get("seed", spec["seed"]))
    network = network_from_spec(r["network"], client_names) if r.get("network") else None
    availability = (
        availability_from_spec(r["availability"], client_names)
        if r.get("availability") else None
    )
    config = RuntimeConfig(
        seed=seed,
        max_concurrency=int(r.get("max_concurrency", 8)),
        dropout_prob=float(r.get("dropout_prob", 0.0)),
        max_retries=int(r.get("max_retries", 2)),
    )
    # policy names resolve through the runtime's registry (sync -> None ->
    # the scheduler's default SyncPolicy), so registered third-party
    # policies are addressable from specs without touching this module
    policy = build_policy(policy_name, r, {
        "aggregator": aggregator,
        "rounds": spec["rounds"],
        "client_names": client_names,
        "network": network,
        "seed": seed,
        "total_tasks": int(r.get("total_tasks", spec["rounds"] * len(client_names))),
        "staleness": polynomial_staleness(float(r.get("staleness_alpha", 0.5))),
    })
    return {
        "runtime": config,
        "policy": policy,
        "network": network,
        "availability": availability,
    }


@dataclasses.dataclass
class Job:
    """A fully-constructed federation, ready to run (or inspect)."""

    spec: dict[str, Any]
    sim: FLSimulator
    init_weights: dict[str, Any]
    history: list[float]
    # legacy AdaptiveQuantizeFilter instances or adaptive pipeline stages —
    # anything exposing last_fmt_by_client
    adaptive_filters: list[Any]

    def run(self) -> dict[str, Any]:
        with kernel_backend_scope(self.spec):
            final = self.sim.run(self.init_weights)
        out = {
            "final_weights": final,
            "history": self.history,
            "messages": self.sim.stats.messages,
            "wire_bytes": self.sim.stats.bytes_sent,
            "round_log": self.sim.round_log,
            "telemetry": self.sim.telemetry(),
        }
        if self.sim.scheduler is not None:
            out["sim_time_s"] = self.sim.sim_time_s
            out["runtime_stats"] = self.sim.scheduler.stats.as_dict()
            out["policy"] = self.sim.scheduler.policy.name
        if self.sim.tracer is not None and isinstance(self.spec.get("trace"), str):
            out["trace"] = self.sim.tracer.write(self.spec["trace"])
        if self.adaptive_filters:
            fmts: dict[str, str] = {}
            for f in self.adaptive_filters:
                fmts.update(f.last_fmt_by_client)
            out["adaptive_fmts"] = fmts
        return out


def build_job(spec: dict[str, Any]) -> Job:
    """Construct the federation a spec describes, without running it.

    ``run_job`` is exactly ``build_job(spec).run()`` — tests use this to
    check the declarative surface against direct FLSimulator construction.
    """
    spec = normalize_spec(spec)
    cfg = get_smoke_config(spec["arch"]) if spec["smoke"] else get_config(spec["arch"])
    model = create_model(cfg)
    datasets = _client_datasets(spec, cfg)
    local_step = _jit_local_step(model, spec["lr"])
    history: list[float] = []

    def make_client(name, data):
        return _train_executor(name, data, spec, local_step, history)

    client_names = [f"site-{i}" for i in range(len(datasets))]
    agg = build_aggregator(aggregator_spec(spec))
    runtime_kwargs = _build_runtime(spec, agg, client_names)
    if spec.get("pipeline"):
        pipelines, adaptive = _build_pipelines(spec, runtime_kwargs.get("network"))
        wire_kwargs: dict[str, Any] = {"pipelines": pipelines}
    else:
        server_filters, client_filters, adaptive = _build_filters(
            spec, network=runtime_kwargs.get("network")
        )
        wire_kwargs = {"server_filters": server_filters, "client_filters": client_filters}
    sim = FLSimulator(
        [make_client(n, d) for n, d in zip(client_names, datasets)],
        agg,
        SimulationConfig(
            num_rounds=spec["rounds"],
            transmission=spec["transmission"],
            chunk_size=int(spec["chunk_mb"] * (1 << 20)),
            driver=spec["driver"],
        ),
        server_streaming_agg=bool(spec.get("server_streaming_agg")),
        trace=bool(spec.get("trace")),
        **wire_kwargs,
        **runtime_kwargs,
    )
    return Job(spec, sim, _host_init(model, spec["seed"]), history, adaptive)


def run_job(spec: dict[str, Any]) -> dict[str, Any]:
    return build_job(spec).run()


def run_job_file(path: str) -> dict[str, Any]:
    with open(path) as fh:
        return run_job(json.load(fh))


def main(argv: Optional[list[str]] = None) -> int:
    """``python -m repro.fl.job spec.json [--trace out.json]`` — run a
    declarative job and print a JSON summary (weights omitted). The
    ``--trace`` flag turns on the span tracer and writes the run's
    Chrome trace-event file, viewable at https://ui.perfetto.dev."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.fl.job",
        description="Run a declarative FL job spec.",
    )
    ap.add_argument("spec", help="path to a JSON job spec")
    ap.add_argument("--trace", metavar="OUT_JSON", default=None,
                    help="record a dual-clock span trace and write Chrome "
                         "trace-event JSON here (open in Perfetto)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    with open(args.spec) as fh:
        spec = json.load(fh)
    if args.trace:
        spec["trace"] = args.trace
    result = run_job(spec)
    result.pop("final_weights", None)
    print(json.dumps(result, indent=1, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
