#!/usr/bin/env python3
"""Bring-up smoke test: the federation round on one TPU, at full width.

    python chip_smoke.py                 # one chip: the run_job path
    python chip_smoke.py --four-chips    # four chips: the mesh plane only

One chip (the default) drives the repository's main path once through
its normal entry point, ``repro.fl.job.run_job``: qwen1.5-0.5b at its
published widths (all 24 layers, random weights from ``--seed``), two
clients, two rounds of two local AdamW steps on 4 x 512 tokens, the
server folding every uplink item as it streams in.

* preflight — the device is a TPU and the kernel backend resolves to
  ``pallas``; the 151936 x 1024 embedding quantizes to the same bytes
  under the ``pallas`` and ``ref`` backends (blockwise8 and nf4); and
  the streaming int8 fold of two full-model uplinks matches a plain
  float32 reference (dequantize, then the sample-weighted mean in
  ``jax.numpy`` at ``highest`` matmul precision).
* phase A — the paper's setting: ``quantize:blockwise8`` both ways and
  the ``quantized-fedavg`` aggregator (int8 quantize kernel, the donated
  ``dequant_accumulate8_into`` fold).
* phase B — ``quantize:nf4`` both ways and the dense ``fedavg`` fold
  (4-bit quantize and dequantize kernels).

Each phase first confirms from the lowered text of every jitted op it
dispatches — the quantize, dequantize and fold ops and the local train
step, whose attention is the flash kernel — that it is a
``tpu_custom_call`` and not interpret mode. It then prints each
round's losses, wire bytes and their ratio to the fp32 payload, wall
seconds per round, compile seconds and persistent-cache hits, and the
device's peak bytes in use. The times are smoke times: one run,
compilation included, not a benchmark.

``--four-chips`` runs the mesh plane of ``repro.launch.fl_train`` and
nothing else: one round over a ``(pods=4, data=1)`` mesh, one pod per
chip, once with the int8 collective and once with fp32 from the same
start and batches, plus a local-only pass that yields each pod's
per-block delta absmax. It checks the int8 round against the fp32
round within the blockwise-int8 error bound those absmaxes give, and
that the pod shards sit on four distinct devices.

The script exits non-zero and prints no result when JAX finds no TPU
(there is no CPU fallback), when it is not run from a checkout of the
repository, or when any phase or check fails. On success the last line
of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
SEQ = 512
BATCH = 4
LOCAL_STEPS = 2
EMBED = "embed.embedding"


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def _import_repo() -> None:
    src = os.path.join(HERE, "src")
    if not os.path.isfile(os.path.join(src, "repro", "fl", "job.py")):
        fail(f"no repro package under {src}: run chip_smoke.py from a "
             "checkout of the repository")
    sys.path.insert(0, src)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is recorded as the time to load)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.hits = 0

    def install(self) -> None:
        import jax

        def on_duration(event: str, secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.hits


def job_spec(seed: int, fmt: str, aggregator: str) -> dict:
    return {
        "arch": ARCH, "smoke": False, "seed": seed,
        "clients": 2, "rounds": 2, "local_steps": LOCAL_STEPS,
        "batch": BATCH, "seq": SEQ, "partition": "dirichlet",
        "transmission": "container", "server_streaming_agg": True,
        "pipeline": {"task_data": [f"quantize:{fmt}"],
                     "task_result": [f"quantize:{fmt}"]},
        "aggregator": aggregator,
    }


def check_lowered(label: str, lowerings: dict) -> None:
    """Each op must lower to a Mosaic ``tpu_custom_call``."""
    for name, lower in lowerings.items():
        if "tpu_custom_call" not in lower().as_text():
            fail(f"{label}: {name} does not lower to a tpu_custom_call "
                 "(interpret mode or a jnp fallback)")
        log(f"{label}: {name} lowers to tpu_custom_call")


def op_lowerings(fmt: str, spec: dict, fold: bool) -> dict:
    """The jitted ops a phase dispatches, lowered as the job calls them:
    the interpret flag is the one ``ops`` derives from the backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.fl.job import _jit_local_step, normalize_spec
    from repro.kernels import ops
    from repro.kernels.fused_dequant_agg import dequant_accumulate8_into_pallas
    from repro.models import create_model
    from repro.optim import adamw_init

    interpret = ops.get_backend() == "pallas_interpret"
    cfg = get_config(spec["arch"])
    emb = (cfg.vocab_size, cfg.d_model)
    n = emb[0] * emb[1]
    f32 = np.dtype(np.float32)
    S = jax.ShapeDtypeStruct
    out: dict = {}
    if fmt == "blockwise8":
        nb = n // ops.BLOCK8
        out["quantize_blockwise8"] = lambda: ops._pallas_q8_full.lower(
            S((n,), jnp.float32), interpret=interpret)
        out["dequantize_blockwise8"] = lambda: ops._pallas_d8_full.lower(
            S((nb, ops.BLOCK8), jnp.int8), S((nb,), jnp.float32),
            shape=emb, dtype=f32, interpret=interpret)
    else:
        nb = n // ops.BLOCK4
        out[f"quantize_4bit[{fmt}]"] = lambda: ops._pallas_q4_full.lower(
            S((n,), jnp.float32), fmt=fmt, interpret=interpret)
        out[f"dequantize_4bit[{fmt}]"] = lambda: ops._pallas_d4_full.lower(
            S((nb, ops.BLOCK4 // 2), jnp.uint8), S((nb,), jnp.float32),
            fmt=fmt, shape=emb, dtype=f32, interpret=interpret)
    if fold:
        nbp = -(-(n // ops.BLOCK8) // ops.ROWS) * ops.ROWS
        out["dequant_accumulate8_into"] = lambda: dequant_accumulate8_into_pallas.lower(
            S((nbp, ops.BLOCK8), jnp.float32), S((nbp, ops.BLOCK8), jnp.int8),
            S((nbp,), jnp.float32), S((), jnp.float32), interpret=interpret)

    def step():
        model = create_model(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        batch = {k: S((spec["batch"], spec["seq"]), jnp.int32)
                 for k in ("tokens", "labels")}
        return _jit_local_step(model, normalize_spec(spec)["lr"]).lower(
            params, jax.eval_shape(adamw_init, params), batch)

    out["local_step (flash attention)"] = step
    return out


def check_backends_bitwise(weights: dict) -> None:
    """The embedding quantizes to identical bytes on both backends."""
    import numpy as np

    from repro.core import quantization as Q
    from repro.kernels import ops

    x = weights[EMBED]
    for fmt in ("blockwise8", "nf4"):
        with ops.backend("pallas"):
            qp = Q.quantize(x, fmt)
            p_pay, p_am = np.asarray(qp.payload), np.asarray(qp.absmax)
        with ops.backend("ref"):
            qr = Q.quantize(x, fmt)
            r_pay, r_am = np.asarray(qr.payload), np.asarray(qr.absmax)
        same = (p_pay.shape == r_pay.shape and np.array_equal(p_pay, r_pay)
                and np.array_equal(p_am.view(np.uint32), r_am.view(np.uint32)))
        if not same:
            diff = int(np.count_nonzero(p_pay != r_pay)) if p_pay.shape == r_pay.shape else -1
            fail(f"{fmt}: pallas and ref payloads of {EMBED} {x.shape} differ "
                 f"({diff} payload bytes differ)")
        log(f"preflight: {fmt} payload of {EMBED} {tuple(x.shape)} is bitwise "
            f"identical under pallas and ref ({p_pay.nbytes + p_am.nbytes} bytes)")


#: the fold and the reference each round an element at most four times
#: (scale = absmax/127, times the code, times the sample weight, plus the
#: running sum; the mean's division by the total weight), half an ulp
#: each, so they agree within 8 ulps of the tensor's largest magnitude
FOLD_ULPS = 8


def check_fold(weights_a: dict, weights_b: dict, w_a: float, w_b: float) -> None:
    """Two full-model uplinks through the streaming int8 fold vs float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.quantization import quantize_batch
    from repro.fl.aggregator import QuantizedFedAvgAggregator

    fmt_for = {k: "blockwise8" for k in weights_a}
    ups = [quantize_batch(weights_a, fmt_for), quantize_batch(weights_b, fmt_for)]
    agg = QuantizedFedAvgAggregator()
    for up, w in zip(ups, (w_a, w_b)):
        weight = agg.begin({"num_samples": w})
        for name, qt in up.items():
            agg.accept_item(name, qt, weight)
    got = agg.finish()
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for name in weights_a:
            deq = []
            for up in ups:
                qt = up[name]
                x = jnp.asarray(qt.payload).astype(jnp.float32) * (
                    jnp.asarray(qt.absmax)[:, None] / 127.0)
                n = int(np.prod(qt.orig_shape))
                deq.append(x.reshape(-1)[:n].reshape(qt.orig_shape))
            want = np.asarray((w_a * deq[0] + w_b * deq[1]) / (w_a + w_b))
            err = float(np.max(np.abs(got[name] - want)))
            tol = FOLD_ULPS * float(np.finfo(np.float32).eps) * float(np.max(np.abs(want)))
            if not err <= tol:
                fail(f"streaming fold of {name} is {err:.3e} from the float32 "
                     f"reference (tolerance {tol:.3e})")
            worst = max(worst, err / tol if tol else 0.0)
    log(f"preflight: streaming int8 fold of 2 full-model uplinks (weights {w_a:g}, "
        f"{w_b:g}) matches the float32 reference on all {len(weights_a)} tensors "
        f"(tolerance {FOLD_ULPS} ulps of each tensor's max; worst {worst:.3f} of it)")


def device_peak_bytes() -> int | None:
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def run_phase(label: str, spec: dict, meter: CompileMeter) -> None:
    import numpy as np

    from repro.fl.job import run_job

    c0, h0 = meter.mark()
    t0 = time.perf_counter()
    result = run_job(spec)
    wall = time.perf_counter() - t0
    c1, h1 = meter.mark()
    losses = result["history"]
    if len(losses) != spec["rounds"] * spec["clients"]:
        fail(f"{label}: {len(losses)} losses for {spec['rounds']} rounds x "
             f"{spec['clients']} clients")
    if not all(math.isfinite(v) for v in losses):
        fail(f"{label}: non-finite loss in {losses}")
    for name, arr in result["final_weights"].items():
        if not np.all(np.isfinite(arr)):
            fail(f"{label}: final weights {name} are not finite")
    traffic = result["telemetry"]["traffic"]
    for r in result["round_log"]:
        rnd = r["round"]
        per = losses[rnd * spec["clients"]:(rnd + 1) * spec["clients"]]
        log(f"{label}: round {rnd} losses {[round(v, 6) for v in per]} "
            f"wall {r['wall_s']:.3f} s (smoke time, compile included, not a benchmark)")
    ratio = traffic["bytes_sent"] / traffic["payload_bytes"]
    peak = device_peak_bytes()
    log(f"{label}: wire_bytes {result['wire_bytes']} over {traffic['messages']} "
        f"messages; fp32 payload bytes {traffic['payload_bytes']}; ratio {ratio:.6f}")
    log(f"{label}: run_job {wall:.3f} s (smoke time); backend compile "
        f"{c1 - c0:.3f} s, persistent-cache hits {h1 - h0}")
    log(f"{label}: device peak_bytes_in_use {peak} (since process start)")
    if peak is None:
        fail(f"{label}: the device reports no peak_bytes_in_use")
    if peak >= 16 * 10**9:
        fail(f"{label}: device peak {peak} bytes is not under 16 GB")


def one_chip(seed: int) -> dict:
    import jax

    from repro.fl.job import initial_weights
    from repro.kernels import ops

    backend = ops.get_backend()
    if backend != "pallas":
        fail(f"kernel backend resolves to {backend!r}, not 'pallas' "
             "(REPRO_KERNEL_BACKEND must be unset or 'auto' / 'pallas')")
    log(f"preflight: kernel backend {backend}")
    meter = CompileMeter()
    meter.install()
    spec_a = job_spec(seed, "blockwise8", "quantized-fedavg")
    spec_b = job_spec(seed, "nf4", "fedavg")

    t0 = time.perf_counter()
    w_a = initial_weights(spec_a)
    w_b = initial_weights({**spec_a, "seed": seed + 1})
    n_params = sum(int(v.size) for v in w_a.values())
    log(f"preflight: {ARCH} full width, {len(w_a)} tensors, {n_params} "
        f"parameters ({time.perf_counter() - t0:.3f} s to initialise two seeds)")
    check_backends_bitwise(w_a)
    samples = spec_a["batch"] * spec_a["local_steps"]
    check_fold(w_a, w_b, float(samples), float(3 * samples))
    del w_a, w_b

    check_lowered("phase A", op_lowerings("blockwise8", spec_a, fold=True))
    run_phase("phase A (blockwise8, quantized-fedavg)", spec_a, meter)
    check_lowered("phase B", op_lowerings("nf4", spec_b, fold=False))
    run_phase("phase B (nf4, fedavg)", spec_b, meter)
    return {"count": len(jax.devices())}


# ---------------------------------------------------------------------------
# four chips: the mesh plane
# ---------------------------------------------------------------------------

def mesh_programs(model, mesh, lr: float):
    """The mesh plane's rounds (int8, fp32), and a local-only pass that
    returns each pod's per-block absmax of its parameter delta — blocked
    exactly as the int8 collective blocks the flattened delta."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import collectives as C
    from repro.launch.fl_train import make_fl_round, make_local_train

    rounds = {agg: make_fl_round(model, local_steps=LOCAL_STEPS, lr=lr, agg=agg,
                                 mesh=mesh)
              for agg in ("int8", "fp32")}
    local_train = make_local_train(model, lr)

    def local_absmax(params, opt_state, batches):
        batches = jax.tree_util.tree_map(lambda x: x[0], batches)
        new, opt_state, _ = local_train(params, opt_state, batches)
        delta = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, params)
        flat, _, _ = C._flatten_tree(delta)
        pad = -flat.shape[0] % C.BLOCK
        blocks = jnp.pad(flat, (0, pad)).reshape(-1, C.BLOCK)
        # the trained state is returned (and dropped by the caller) so its
        # buffers alias the donated inputs, as in the round itself
        return new, opt_state, jnp.max(jnp.abs(blocks), axis=1)[None]

    local = jax.jit(jax.shard_map(
        local_absmax, mesh=mesh, in_specs=(P(), P(), P("pod")),
        out_specs=(P(), P(), P("pod")), check_vma=False), donate_argnums=(0, 1))
    return rounds, local


def _flat(tree) -> "np.ndarray":
    import jax
    import numpy as np

    return np.concatenate([np.asarray(l, np.float32).reshape(-1)
                           for l in jax.tree_util.tree_leaves(tree)])


def four_chips(seed: int) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core.collectives import BLOCK
    from repro.data import dirichlet_partition
    from repro.models import create_model
    from repro.optim import adamw_init

    devs = jax.devices()
    if len(devs) != 4:
        fail(f"--four-chips needs 4 devices, JAX reports {len(devs)}")
    pods = 4
    mesh = Mesh(np.array(devs).reshape(pods, 1), ("pod", "data"))
    cfg = get_config(ARCH)
    model = create_model(cfg)
    lr = 1e-3
    rep = NamedSharding(mesh, P())
    init = jax.jit(model.init, out_shardings=rep)
    opt_init = jax.jit(adamw_init, out_shardings=rep)
    datasets = dirichlet_partition(cfg.vocab_size, SEQ, pods, alpha=0.5, seed=seed)
    samples = [[ds.sample(BATCH) for _ in range(LOCAL_STEPS)] for ds in datasets]
    host_batches = {k: np.stack([np.stack([s[k] for s in pod]) for pod in samples])
                    for k in ("tokens", "labels")}
    batches = jax.device_put(host_batches, NamedSharding(mesh, P("pod")))
    where = {s.device for s in batches["tokens"].addressable_shards}
    if len(where) != pods:
        fail(f"pod batch shards sit on {len(where)} device(s), not {pods}")
    rounds, local = mesh_programs(model, mesh, lr)
    key = jax.random.PRNGKey(seed)
    log(f"four chips: mesh (pod={pods}, data=1) over devices "
        f"{sorted(d.id for d in where)}; {ARCH} full width; {LOCAL_STEPS} local "
        f"steps x batch {BATCH} x seq {SEQ} per pod")

    t0 = time.perf_counter()
    params = init(key)
    # every program's outputs are held by one name and dropped before
    # the next program loads: a leftover replica of the trained state
    # is 7.4 GB of a chip's 16
    result = local(params, opt_init(params), batches)
    shard_devs = {s.device for s in result[2].addressable_shards}
    if len(shard_devs) != pods:
        fail(f"per-pod results sit on {len(shard_devs)} device(s), not {pods}")
    absmax = np.asarray(result[2])
    del result, params
    log(f"four chips: local-only pass {time.perf_counter() - t0:.3f} s (smoke time, "
        f"compile included); per-pod results on {len(shard_devs)} distinct devices")
    out = {}
    for agg in ("fp32", "int8"):
        t0 = time.perf_counter()
        params = init(key)
        result = rounds[agg](params, opt_init(params), batches)
        out[agg], loss = _flat(result[0]), result[2]
        del result, params
        log(f"four chips: {agg} round {time.perf_counter() - t0:.3f} s (smoke time, "
            f"compile included); mean local loss {float(loss):.6f}")
        if not math.isfinite(float(loss)) or not np.all(np.isfinite(out[agg])):
            fail(f"{agg} round produced non-finite values")
    # each pod's delta is rounded to its block's absmax/127 step, so the
    # int8 mean moves by at most mean_p(absmax_pb)/254 per block; the
    # sum with the start weights rounds once more
    n = out["fp32"].size
    bound = np.repeat(absmax.mean(axis=0) / 254.0, BLOCK)[:n]
    err = np.abs(out["int8"] - out["fp32"])
    eps = float(np.finfo(np.float32).eps)
    allowed = bound * (1 + 1e-3) + 2 * eps * np.abs(out["fp32"]) + 1e-30
    bad = err > allowed
    log(f"four chips: {n} parameters; max |int8 - fp32| {float(err.max()):.3e}, "
        f"max bound {float(bound.max()):.3e}, worst use "
        f"{float(np.max(err / allowed)):.3f} of the bound; "
        f"{int(np.count_nonzero(out['int8'] != out['fp32']))} elements differ")
    if bad.any():
        fail(f"int8 round differs from fp32 beyond the blockwise-int8 bound on "
             f"{int(bad.sum())} elements (max excess {float(np.max(err - allowed)):.3e})")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    log(f"four chips: per-device peak_bytes_in_use {peaks}")
    return {"count": len(devs)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the federation round on the chip at full width and "
                    "check it (see the module docstring).")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh-plane phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and data")
    args = ap.parse_args(argv)

    _import_repo()
    import jax

    from repro.utils.jax_env import enable_compile_cache

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found platform {dev.platform!r}, not 'tpu'; this smoke "
             "runs only on the chip (no CPU fallback)")
    log(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
        f"compile cache {cache}")
    info = four_chips(args.seed) if args.four_chips else one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": info["count"]}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
