"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the semantic ground truth: each Pallas kernel's test sweeps
shapes/dtypes and asserts allclose against the function here. They are
also the fallback implementation on platforms where we don't run Pallas
(the codecs in ``repro.core.quantization`` call through ``ops.py`` which
dispatches pallas-vs-ref).

Quantization semantics follow bitsandbytes as used by the paper:

* ``blockwise8``  — symmetric linear int8 over absmax blocks of 4096
  (paper Table II: meta = 4 B absmax per 4096 params -> 1.54 MB for 1.5 G
  params).
* ``fp4`` / ``nf4`` — 4-bit codebook quantization over absmax blocks of 64,
  two codes packed per byte (paper Table II: meta = 4 B per 64 params ->
  89.33 MB).

All block math happens on a 2-D ``(num_blocks, block_size)`` view; callers
(ops.py) handle flattening/padding of arbitrary shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK8 = 4096  # blockwise-int8 block size (bitsandbytes default)
BLOCK4 = 64    # 4-bit block size (bitsandbytes / QLoRA default)

# bitsandbytes FP4 (E2M1-style) codebook, normalized to [-1, 1].
FP4_CODE = np.array(
    [
        0.0, 0.0052083333, 0.6666666667, 1.0,
        0.3333333333, 0.5, 0.1666666667, 0.25,
        -0.0, -0.0052083333, -0.6666666667, -1.0,
        -0.3333333333, -0.5, -0.1666666667, -0.25,
    ],
    dtype=np.float32,
)

# QLoRA NF4 codebook (information-theoretically optimal for N(0,1)).
NF4_CODE = np.array(
    [
        -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
        -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
        0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
        0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
        0.7229568362236023, 1.0,
    ],
    dtype=np.float32,
)


def _sorted_code_and_perm(code: np.ndarray):
    """Sorted codebook + permutation mapping sorted-rank -> code index."""
    order = np.argsort(code, kind="stable")
    return code[order].astype(np.float32), order.astype(np.int32)


# ---------------------------------------------------------------------------
# blockwise int8
# ---------------------------------------------------------------------------

def quantize_blockwise8(x2d: jnp.ndarray):
    """x2d: (nblocks, BLOCK8) float -> (int8 codes, fp32 absmax per block)."""
    x2d = x2d.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x2d), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, 127.0 / absmax, 0.0)
    q = jnp.clip(jnp.round(x2d * scale), -127, 127).astype(jnp.int8)
    return q, absmax[..., 0].astype(jnp.float32)


def dequantize_blockwise8(q: jnp.ndarray, absmax: jnp.ndarray) -> jnp.ndarray:
    """(nblocks, BLOCK8) int8 + (nblocks,) absmax -> fp32."""
    scale = absmax[..., None].astype(jnp.float32) / 127.0
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# 4-bit codebook (fp4 / nf4)
# ---------------------------------------------------------------------------

def _bin_codes(xnorm: jnp.ndarray, code: np.ndarray) -> jnp.ndarray:
    """Nearest-codebook-entry index (uint8 values 0..15) via midpoints.

    Branchless: rank = sum(x > midpoint_i), then a 16-entry gather maps
    the sorted rank back to the original codebook index. Same comparison
    network as the Pallas kernel.
    """
    sorted_code, perm = _sorted_code_and_perm(code)
    mids = (sorted_code[1:] + sorted_code[:-1]) / 2.0  # (15,)
    rank = jnp.zeros(xnorm.shape, dtype=jnp.int32)
    for m in mids.tolist():
        rank = rank + (xnorm > m).astype(jnp.int32)
    # map sorted-rank back to code index: one gather instead of a 16-way
    # select chain (bitwise-identical; perm[rank] == select(rank == r, p))
    idx = jnp.asarray(perm)[rank]
    return idx.astype(jnp.uint8)


def quantize_4bit(x2d: jnp.ndarray, code: np.ndarray):
    """x2d: (nblocks, BLOCK4) -> (packed uint8 (nblocks, BLOCK4//2), absmax)."""
    x2d = x2d.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x2d), axis=-1, keepdims=True)
    inv = jnp.where(absmax > 0, 1.0 / absmax, 0.0)
    xnorm = x2d * inv
    idx = _bin_codes(xnorm, code)
    hi = idx[..., 0::2]
    lo = idx[..., 1::2]
    packed = (hi.astype(jnp.uint8) << 4) | lo.astype(jnp.uint8)
    return packed, absmax[..., 0].astype(jnp.float32)


def dequantize_4bit(packed: jnp.ndarray, absmax: jnp.ndarray, code: np.ndarray) -> jnp.ndarray:
    """(nblocks, BLOCK4//2) packed + absmax -> (nblocks, BLOCK4) fp32."""
    hi = (packed >> 4).astype(jnp.int32)
    lo = (packed & 0xF).astype(jnp.int32)
    nb, half = packed.shape
    idx = jnp.stack([hi, lo], axis=-1).reshape(nb, half * 2)
    # vectorized codebook lookup: one 16-entry gather (bitwise-identical
    # to the old 16-way select chain, ~4x fewer VPU passes)
    vals = jnp.asarray(code, dtype=jnp.float32)[idx]
    return vals * absmax[..., None].astype(jnp.float32)


# ---------------------------------------------------------------------------
# fused dequantize + weighted accumulate (server-side FedAvg on quantized
# payloads; "beyond-paper": aggregation reads int8 directly, never
# materializing K fp32 copies)
# ---------------------------------------------------------------------------

def dequant_accumulate8(
    qs: jnp.ndarray, absmaxes: jnp.ndarray, weights: jnp.ndarray
) -> jnp.ndarray:
    """qs: (K, nblocks, BLOCK8) int8, absmaxes: (K, nblocks), weights: (K,)

    -> (nblocks, BLOCK8) fp32 = sum_k w_k * dequant(qs[k]).

    Elementwise multiply-adds, not a contraction: as an einsum the TPU
    would run it on the MXU at default precision, rounding the fp32
    scales to bf16 — an error of up to absmax/256 per element, the size
    of the quantization step itself.
    """
    scale = (absmaxes.astype(jnp.float32) / 127.0) * weights.astype(jnp.float32)[:, None]
    return jnp.sum(qs.astype(jnp.float32) * scale[:, :, None], axis=0)


# ---------------------------------------------------------------------------
# attention oracle (for the flash-attention kernel)
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal=True, window=None):
    """q: (B,H,Sq,hd); k,v: (B,KV,Sk,hd); plain softmax attention."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, Sq, hd).astype(jnp.float32)
    s = jnp.einsum("bkgsd,bktd->bkgst", qg, k.astype(jnp.float32)) / np.sqrt(hd)
    qi = jnp.arange(Sq)[:, None]
    ki = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = mask & (ki <= qi)
    if window is not None:
        mask = mask & (qi - ki < window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", p, v.astype(jnp.float32))
    return out.reshape(B, H, Sq, hd).astype(q.dtype)
