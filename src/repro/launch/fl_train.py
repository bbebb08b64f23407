"""Federated training on the multi-pod mesh — the mesh view of the paper.

Each pod is one FL site: it holds a model replica (sharded over its own
data/model axes), runs ``local_steps`` of AdamW on its own (non-IID-able)
data shard, then the round closes with a cross-pod aggregation of the
parameter delta:

    --agg fp32        paper-faithful full-precision aggregation (pmean)
    --agg int8        quantized collective (blockwise-int8 wire, fp32 agg)
    --agg int8-bucket quantized + bucketed (streaming) collective

Demo (CPU, fake devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
      python -m repro.launch.fl_train --arch qwen1.5-0.5b --smoke \
      --rounds 5 --local-steps 2 --pods 2 --agg int8
"""
from __future__ import annotations

import argparse
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import collectives as C
from repro.data import dirichlet_partition
from repro.models import create_model
from repro.optim import adamw_init, adamw_update


def make_local_train(model, lr: float):
    """A pod's local phase: AdamW over a stacked ``(local_steps, ...)``
    batch pytree with ``lax.scan``; returns (params, opt_state, losses)."""

    def local_train(params, opt_state, batches):
        def one_step(carry, batch):
            params, opt_state = carry
            (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
            params, opt_state, _ = adamw_update(params, grads, opt_state, jnp.float32(lr))
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(one_step, (params, opt_state), batches)
        return params, opt_state, losses

    return local_train


def make_fl_round(model, *, local_steps: int, lr: float, agg: str, mesh):
    """One federated round as a single jitted program:

    shard_map over 'pod' (each pod trains locally), then cross-pod
    aggregation of the parameter delta with the configured wire format.
    """
    local_train = make_local_train(model, lr)

    def fl_round(params, opt_state, batches):
        # shard_map keeps the (now size-1) pod dim on the batch stack
        batches = jax.tree_util.tree_map(lambda x: x[0], batches)
        # ---- local phase (per pod) ----
        start = params
        params, opt_state, losses = local_train(params, opt_state, batches)
        # ---- aggregation phase (cross-pod; the FL communication) ----
        delta = jax.tree_util.tree_map(
            lambda new, old: new.astype(jnp.float32) - old.astype(jnp.float32), params, start
        )
        if agg == "fp32":
            delta = C.fp32_fedavg_tree(delta, axis_name="pod")
        elif agg == "int8":
            delta = C.quantized_fedavg_tree(delta, axis_name="pod")
        elif agg == "int8-bucket":
            delta = C.quantized_fedavg_tree(delta, axis_name="pod", bucket_bytes=8 << 20)
        else:
            raise ValueError(agg)
        params = jax.tree_util.tree_map(
            lambda old, d: (old.astype(jnp.float32) + d).astype(old.dtype), start, delta
        )
        return params, opt_state, jnp.mean(losses)

    pspec = P()  # params replicated within pod; pod axis handled by shard_map
    batch_spec = P("pod")  # leading dim = pod-local batches

    fl_round_sm = jax.shard_map(
        fl_round,
        mesh=mesh,
        in_specs=(pspec, pspec, batch_spec),
        out_specs=(pspec, pspec, pspec),
        check_vma=False,
    )
    return jax.jit(fl_round_sm, donate_argnums=(0, 1))


def run(args) -> dict[str, Any]:
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = create_model(cfg)
    mesh = jax.make_mesh((args.pods, jax.device_count() // args.pods), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    params = model.init(jax.random.PRNGKey(args.seed))
    opt_state = adamw_init(params)
    datasets = dirichlet_partition(
        cfg.vocab_size, args.seq, args.pods, alpha=args.alpha, seed=args.seed
    )
    round_fn = make_fl_round(
        model, local_steps=args.local_steps, lr=args.lr, agg=args.agg, mesh=mesh
    )
    history = []
    for rnd in range(args.rounds):
        # stack per-pod local batches: (pods, local_steps, B, S) — sample
        # ONCE per (pod, step) so tokens and labels stay paired
        samples = [
            [ds.sample(args.batch) for _ in range(args.local_steps)] for ds in datasets
        ]
        batches = {
            k: jnp.stack(
                [jnp.stack([jnp.asarray(s[k]) for s in pod]) for pod in samples]
            )
            for k in ("tokens", "labels")
        }
        t0 = time.time()
        params, opt_state, loss = round_fn(params, opt_state, batches)
        loss = float(loss)
        history.append(loss)
        print(f"round {rnd:3d} agg={args.agg:11s} loss={loss:.4f} ({time.time()-t0:.1f}s)")
    return {"history": history, "params": params}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--agg", choices=["fp32", "int8", "int8-bucket"], default="int8")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    out = run(args)
    print(f"final loss {out['history'][-1]:.4f} (start {out['history'][0]:.4f})")


if __name__ == "__main__":
    main()
