"""Plain reference of one federation round, and the cell's weights.

Nothing here imports the system under test. The round is written from
the configuration file and the traffic file alone, and depends on no
architecture: the model (its leaves, loss, FLOPs and CPU cut) is the
reference module of the configuration's ``family``,
``refmodels/<family>.py``, which :func:`model_ref` loads by file path
(see ``refmodels/dense.py`` for the names each module exports).

* weights: every leaf of the module's ``leaf_specs`` drawn from the
  seed in one jitted call on the device (normal times the leaf's init
  scale; std 0 means zeros), float32, the dtype the configuration
  trains in;
* downlink: each leaf through the hop's codec and back (blockwise8:
  symmetric int8 over absmax blocks of 4096; nf4: the QLoRA codebook
  over absmax blocks of 64, nearest entry; no stage: unchanged);
* each client: its local AdamW steps on its own token rows, on the
  module's ``loss_fn``;
* uplink: the trained leaves through the hop's codec and back, folded
  on the host in float32 as the sample-weighted mean.

``dtype=jnp.bfloat16`` computes the clients' training in bfloat16
throughout and rounds the folded result to bfloat16: the control that
the comparison must reject. ``fault`` plants one of the faults the
comparison must catch (see :data:`FAULTS`).
"""
from __future__ import annotations

import functools
import math
import os
import types
from collections.abc import Callable
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

import cells

#: one reference module per architecture, found by the configuration's
#: ``"family"`` (the program's own architecture field)
REFMODELS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "refmodels")
#: the names every reference module exports
INTERFACE = ("leaf_specs", "loss_fn", "step_flops", "tiny")

BLOCK8 = 4096
BLOCK4 = 64
NF4_CODE = np.array([
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0,
], dtype=np.float32)

#: faults planted in the reference put in the program's place:
#: ``stale`` returns the round's start weights; ``half_batch`` trains
#: every step on the first half of its rows; ``one_client`` folds only
#: the first client's uplink (the second exchange left out);
#: ``codec_bypass`` sends both hops unquantized
FAULTS = ("stale", "half_batch", "one_client", "codec_bypass")


# ---------------------------------------------------------------------------
# leaves and weights
# ---------------------------------------------------------------------------

def model_ref(config: dict) -> types.ModuleType:
    """The reference module of the configuration's ``"family"``,
    loaded once from ``refmodels/<family>.py`` by file path."""
    return _load_ref(REFMODELS, config.get("family"))


@functools.lru_cache(maxsize=None)
def _load_ref(directory: str, name: Optional[str]) -> types.ModuleType:
    known = sorted(f[:-3] for f in os.listdir(directory)
                   if f.endswith(".py") and not f.startswith("_"))
    if name not in known:
        raise KeyError(f"family {name!r} has no reference module; "
                       f"known reference modules: {known}")
    mod = cells.load_module(os.path.join(directory, f"{name}.py"), f"chipbench_refmodel_{name}")
    missing = [n for n in INTERFACE if not callable(getattr(mod, n, None))]
    if missing:
        raise AttributeError(f"reference module {name!r} does not export {missing}")
    return mod


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def init_weights(model: dict, seed: int) -> dict[str, jax.Array]:
    """All leaves from ``seed`` in one jitted call, on the device, float32."""
    specs = model_ref(model).leaf_specs(model)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, std)) in enumerate(specs.items()):
            if std == 0.0:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32) * std
        return out

    return make(seed_key(seed))


# ---------------------------------------------------------------------------
# codecs (one leaf, round trip)
# ---------------------------------------------------------------------------

def _blocks(x: jax.Array, block: int) -> jax.Array:
    flat = x.astype(jnp.float32).reshape(-1)
    pad = -flat.shape[0] % block
    return jnp.pad(flat, (0, pad)).reshape(-1, block)


@functools.partial(jax.jit, static_argnames=("fmt",))
def codec_roundtrip(x: jax.Array, fmt: Optional[str]) -> jax.Array:
    """``x`` encoded by ``fmt`` and decoded again, float32."""
    if fmt is None:
        return x.astype(jnp.float32)
    n = x.size
    if fmt == "blockwise8":
        b = _blocks(x, BLOCK8)
        absmax = jnp.max(jnp.abs(b), axis=1, keepdims=True)
        scale = jnp.where(absmax > 0, 127.0 / absmax, 0.0)
        q = jnp.clip(jnp.round(b * scale), -127, 127)
        out = q * (absmax / 127.0)
    elif fmt == "nf4":
        b = _blocks(x, BLOCK4)
        absmax = jnp.max(jnp.abs(b), axis=1, keepdims=True)
        xn = b * jnp.where(absmax > 0, 1.0 / absmax, 0.0)
        code = jnp.asarray(np.sort(NF4_CODE))
        mids = (code[1:] + code[:-1]) / 2.0
        # nearest codebook entry: the number of midpoints below x
        rank = jnp.searchsorted(mids, xn, side="left")
        out = code[rank] * absmax
    else:
        raise ValueError(f"no reference codec for {fmt!r}")
    return out.reshape(-1)[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# local step
# ---------------------------------------------------------------------------

def make_local_step(model: dict, opt: dict, dtype) -> Callable:
    """One AdamW step on the model's loss (global-norm clip, decoupled
    weight decay, bias correction) on state ``(p, m, v)`` held in
    ``dtype``; returns the new state, the loss and each leaf's gradient
    norm before clipping."""
    loss_fn = model_ref(model).loss_fn
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    wd, lr, clip = float(opt["weight_decay"]), float(opt["lr"]), float(opt["clip_norm"])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, count, tokens):
        loss, g = jax.value_and_grad(loss_fn)(p, tokens, model, dtype)
        g = {k: x.astype(dtype) for k, x in g.items()}
        gnorms = {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                  for k, x in g.items()}
        gn = jnp.sqrt(sum(jnp.square(n) for n in gnorms.values()))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9)).astype(dtype)
        t = (count + 1).astype(jnp.float32)
        bc1 = (1.0 - b1 ** t).astype(dtype)
        bc2 = (1.0 - b2 ** t).astype(dtype)
        newp, newm, newv = {}, {}, {}
        for k in p:
            gk = g[k] * scale
            newm[k] = b1 * m[k] + (1 - b1) * gk
            newv[k] = b2 * v[k] + (1 - b2) * jnp.square(gk)
            delta = (newm[k] / bc1) / (jnp.sqrt(newv[k] / bc2) + eps) + wd * p[k]
            newp[k] = p[k] - lr * delta
        return newp, newm, newv, loss.astype(jnp.float32), gnorms

    return step


# ---------------------------------------------------------------------------
# one round
# ---------------------------------------------------------------------------

def reference_round(
    w0: dict[str, np.ndarray],
    model: dict,
    traffic: dict,
    batch_of: Callable[[int, int], np.ndarray],
    rnd: int = 0,
    dtype=jnp.float32,
    fault: Optional[str] = None,
) -> dict[str, Any]:
    """Round ``rnd`` from the host weights ``w0``.

    ``batch_of(client, key)`` gives the int32 token rows of one local
    step (key = rnd * local_steps + step). Returns the last local loss
    of each client, every step's loss, the new weights (host float32)
    and each leaf's gradient norm at the first client's first step."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    if fault == "stale":
        return {"losses": [math.nan] * traffic["clients"], "step_losses": [],
                "weights": w0, "grad_norms": None}
    down, up = traffic["downlink"], traffic["uplink"]
    if fault == "codec_bypass":
        down = up = None
    steps = int(traffic["local_steps"])
    step = make_local_step(model, traffic["optimizer"], dtype)
    acc: dict[str, np.ndarray] = {}
    total = 0.0
    losses, step_losses, grad_norms = [], [], None
    clients = range(1 if fault == "one_client" else traffic["clients"])
    for c in clients:
        p = {k: codec_roundtrip(jnp.asarray(a), down).astype(dtype) for k, a in w0.items()}
        m = {k: jnp.zeros_like(a) for k, a in p.items()}
        v = {k: jnp.zeros_like(a) for k, a in p.items()}
        loss = None
        for s in range(steps):
            tokens = batch_of(c, rnd * steps + s)
            if fault == "half_batch":
                tokens = tokens[: tokens.shape[0] // 2]
            p, m, v, loss, gn = step(p, m, v, jnp.int32(s), jnp.asarray(tokens))
            step_losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {k: float(x) for k, x in gn.items()}
        del m, v
        losses.append(float(loss))
        weight = float(traffic["batch"] * steps)
        total += weight
        for k in list(p):
            x = np.asarray(codec_roundtrip(p.pop(k).astype(jnp.float32), up))
            if k in acc:
                acc[k] += x * np.float32(weight)
            else:
                acc[k] = x * np.float32(weight)
    out = {}
    for k in list(acc):
        mean = acc.pop(k) / np.float32(total)
        if dtype != jnp.float32:
            mean = np.asarray(jnp.asarray(mean).astype(dtype).astype(jnp.float32))
        out[k] = mean.astype(np.float32)
    return {"losses": losses, "step_losses": step_losses, "weights": out,
            "grad_norms": grad_norms}
