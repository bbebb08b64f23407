"""The numbers ``correct`` compares, from one round of the program and
of the reference (or of the control, or of a planted fault).

Each side is summarised the same way by :func:`round_summary`: each
client's last local loss, per leaf the norm of the round's change
``W1 - W0``, and the change at a sample of each leaf's elements (up to
:data:`SAMPLE` positions a leaf, drawn from the seed; a smaller leaf is
taken whole).

* ``loss_gap``: the largest gap, over clients, between the program's
  and the reference's last local loss (nats).
* ``delta_norm_gap``: over the counted leaves, the largest gap between
  the program's and the reference's norm of the round's change, over
  the larger of that leaf's reference norm and the median leaf's.
* ``delta_diff``: over the counted leaves, the root mean square of the
  difference between the program's and the reference's change at the
  sampled elements, over the larger of the reference change's root mean
  square in that leaf and in the median leaf. Unlike the gap of norms it
  sees the direction of the change, and a codec that was bypassed or
  altered on either hop.
* ``nonfinite``: non-finite losses and weights the program produced.

A leaf counts where the reference's first gradient of it is at least
a thousandth of the median leaf's: a key's bias has no gradient under
softmax, and Adam moves it by round-off alone.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

GRAD_FLOOR = 1e-3
SAMPLE = 1 << 20


@jax.jit
def _leaf_summary(w1, w0):
    d = w1.astype(jnp.float32) - w0
    return jnp.sqrt(jnp.sum(jnp.square(d))), jnp.sum(~jnp.isfinite(d))


def sample_index(w0: dict[str, Any], seed: int) -> dict[str, Any]:
    """Per leaf, the flat positions sampled (``None``: the whole leaf)."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 0x5A4D])
    out = {}
    for k, a in w0.items():
        n = int(np.prod(np.shape(a)))
        out[k] = None if n <= SAMPLE else np.sort(rng.integers(0, n, SAMPLE))
    return out


def _take(a: Any, idx: Any) -> np.ndarray:
    flat = np.asarray(a).reshape(-1)
    return (flat if idx is None else flat[idx]).astype(np.float32)


def round_summary(losses: list[float], w1: dict[str, Any],
                  w0: dict[str, np.ndarray], idx: dict[str, Any]) -> dict[str, Any]:
    """Leaf by leaf: the norm of ``w1 - w0`` and its non-finite elements
    (on the device), and ``w1 - w0`` at the sampled positions ``idx``."""
    norms, sample, nonfinite = {}, {}, 0
    for k, a in w0.items():
        norm, bad = _leaf_summary(jnp.asarray(w1[k]), jnp.asarray(a))
        norms[k] = float(norm)
        nonfinite += int(bad)
        sample[k] = _take(w1[k], idx[k]) - _take(a, idx[k])
    nonfinite += sum(1 for v in losses if not math.isfinite(v))
    return {"losses": list(losses), "norms": norms, "sample": sample,
            "nonfinite": nonfinite}


def counted_leaves(grad_norms: dict[str, float]) -> list[str]:
    med = float(np.median(list(grad_norms.values())))
    return sorted(k for k, g in grad_norms.items() if g >= GRAD_FLOOR * med)


def _rms(x: np.ndarray) -> float:
    return float(np.sqrt(np.mean(np.square(x, dtype=np.float64))))


def _worst(values) -> float:
    """The largest value; a non-finite one reads as infinite."""
    return max(v if math.isfinite(v) else math.inf for v in values)


def compared(prog: dict[str, Any], ref: dict[str, Any],
             counted: list[str]) -> dict[str, float]:
    """The numbers of the module docstring, program against reference."""
    loss_gap = max((abs(a - b) if math.isfinite(a) else math.inf)
                   for a, b in zip(prog["losses"], ref["losses"]))
    nr = {k: ref["norms"][k] for k in counted}
    med = float(np.median(list(nr.values())))
    norm_gap = _worst(abs(prog["norms"][k] - nr[k]) / max(nr[k], med) for k in counted)
    rr = {k: _rms(ref["sample"][k]) for k in counted}
    rmed = float(np.median(list(rr.values())))
    diff = _worst(_rms(prog["sample"][k] - ref["sample"][k]) / max(rr[k], rmed)
                  for k in counted)
    return {"loss_gap": loss_gap, "delta_norm_gap": norm_gap, "delta_diff": diff,
            "nonfinite": float(prog["nonfinite"])}


def verdict(numbers: dict[str, float], limits: dict[str, Any]) -> tuple[bool, dict]:
    """Each limited number beside its limit; correct when every one is
    within its limit (a number that is NaN or missing fails)."""
    checks = {}
    ok = True
    for name, spec in limits["numbers"].items():
        value = numbers.get(name, math.nan)
        limit = spec["limit"]
        passed = limit is not None and math.isfinite(value) and value <= limit
        ok = ok and passed
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
