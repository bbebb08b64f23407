"""Pallas TPU kernels for 4-bit codebook quantization (fp4 / nf4).

TPU adaptation (DESIGN.md §3): bitsandbytes' CUDA path binary-searches the
codebook per element and packs nibbles with warp shuffles. TPU has neither
fast per-element gathers in VREG nor warp shuffles (Mosaic lowers no 1-D
gather and no lane-strided slice), so:

* binning is a **branchless comparison network** over the 15 sorted-
  codebook midpoints. The sorted-rank -> code-index permutation is folded
  into the network: each compare adds ``perm[i+1] - perm[i]`` instead of
  1, so the sum telescopes to ``perm[rank]`` (the compares are monotone
  in the midpoint order) with no lookup table at all — bitwise equal to
  the reference's ``perm[rank]`` gather.
* the kernels work on the **block-transposed** view ``(64, nblocks)``:
  one quant block per lane column. A row-major ``(nblocks, 64)`` array
  would pad its 64-wide minor dim to the 128-lane tile and double its
  HBM footprint, and its per-block absmax would be a lane-sparse column.
  Transposed, the absmax is a sublane reduction that lands lane-dense in
  a ``(1, nblocks)`` row, and packing pairs rows ``2j`` and ``2j+1``.
  The wrappers transpose in XLA, outside the kernel.
* nibble packing and unpacking are exact 0/1 selection matmuls on the
  MXU: ``P (32, 64) @ idx (64, L)`` with ``P[j, 2j] = 16``,
  ``P[j, 2j+1] = 1`` yields ``16 * idx[2j] + idx[2j+1]``; its transpose
  pair re-interleaves on decode. Every operand is an integer below 256,
  so bf16 inputs with fp32 accumulation are exact.
* decode maps the 4-bit index to its codebook value with a 16-way select
  chain over compile-time constants (selects copy, so the values are the
  codebook entries bit for bit).

Each grid step processes ``ROWS4 = 256`` blocks: a (64, 256) fp32 tile =
64 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.ref import FP4_CODE, NF4_CODE, _sorted_code_and_perm

BLOCK4 = 64
ROWS4 = 256  # blocks per grid step


def _pack_matrix() -> jnp.ndarray:
    """(32, 64) bf16 selection matrix: row j takes 16x row 2j plus row
    2j+1 (built from iotas because Pallas kernels cannot capture array
    constants)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (BLOCK4 // 2, BLOCK4), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (BLOCK4 // 2, BLOCK4), 1)
    m = jnp.where(c == 2 * r, 16.0, jnp.where(c == 2 * r + 1, 1.0, 0.0))
    return m.astype(jnp.bfloat16)


def _spread_matrix(parity: int) -> jnp.ndarray:
    """(64, 32) bf16 0/1 matrix sending row j to row 2j + parity."""
    r = jax.lax.broadcasted_iota(jnp.int32, (BLOCK4, BLOCK4 // 2), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (BLOCK4, BLOCK4 // 2), 1)
    return jnp.where(r == 2 * c + parity, 1.0, 0.0).astype(jnp.bfloat16)


def _make_quant_kernel(code: np.ndarray):
    sorted_code, perm = _sorted_code_and_perm(code)
    mids = ((sorted_code[1:] + sorted_code[:-1]) / 2.0).tolist()
    base = int(perm[0])
    steps = [int(b) - int(a) for a, b in zip(perm[:-1], perm[1:])]

    def kernel(x_ref, packed_ref, absmax_ref):
        x = x_ref[...].astype(jnp.float32)                    # (64, L)
        absmax = jnp.max(jnp.abs(x), axis=0, keepdims=True)   # (1, L)
        inv = jnp.where(absmax > 0.0, 1.0 / absmax, 0.0)
        xn = x * inv
        idx = jnp.full(xn.shape, base, jnp.int32)
        for m, d in zip(mids, steps):                         # 15 VPU compares
            idx = idx + jnp.where(xn > m, d, 0)
        pairs = jnp.dot(
            _pack_matrix(), idx.astype(jnp.float32).astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )                                                     # (32, L), exact
        packed_ref[...] = pairs.astype(jnp.int32).astype(jnp.uint8)
        absmax_ref[...] = absmax

    return kernel


def _make_dequant_kernel(code: np.ndarray):
    values = [float(c) for c in code]

    def kernel(packed_ref, absmax_ref, out_ref):
        packed = packed_ref[...].astype(jnp.int32)            # (32, L)
        hi = (packed >> 4).astype(jnp.float32).astype(jnp.bfloat16)
        lo = (packed & 0xF).astype(jnp.float32).astype(jnp.bfloat16)
        idx = (
            jnp.dot(_spread_matrix(0), hi, preferred_element_type=jnp.float32)
            + jnp.dot(_spread_matrix(1), lo, preferred_element_type=jnp.float32)
        ).astype(jnp.int32)                                   # (64, L), exact
        vals = jnp.zeros(idx.shape, jnp.float32)
        for i, c in enumerate(values):                        # 16-way select
            vals = jnp.where(idx == i, jnp.float32(c), vals)
        out_ref[...] = vals * absmax_ref[...]

    return kernel


def _codebook(fmt: str) -> np.ndarray:
    if fmt == "fp4":
        return FP4_CODE
    if fmt == "nf4":
        return NF4_CODE
    raise ValueError(f"unknown 4-bit format: {fmt}")


@functools.partial(jax.jit, static_argnames=("fmt", "interpret"))
def quantize_4bit_pallas(x2d: jnp.ndarray, *, fmt: str, interpret: bool = False):
    """x2d: (nblocks, 64); nblocks must be a multiple of ROWS4.

    Returns ((nblocks, 32) packed uint8, (nblocks,) fp32 absmax)."""
    nblocks = x2d.shape[0]
    assert x2d.shape[1] == BLOCK4 and nblocks % ROWS4 == 0, x2d.shape
    packed_t, absmax = pl.pallas_call(
        _make_quant_kernel(_codebook(fmt)),
        grid=(nblocks // ROWS4,),
        in_specs=[pl.BlockSpec((BLOCK4, ROWS4), lambda i: (0, i))],
        out_specs=[
            pl.BlockSpec((BLOCK4 // 2, ROWS4), lambda i: (0, i)),
            pl.BlockSpec((1, ROWS4), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BLOCK4 // 2, nblocks), jnp.uint8),
            jax.ShapeDtypeStruct((1, nblocks), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_4bit",
    )(x2d.T)
    return packed_t.T, absmax.reshape(nblocks)


@functools.partial(jax.jit, static_argnames=("fmt", "interpret"))
def dequantize_4bit_pallas(
    packed: jnp.ndarray, absmax: jnp.ndarray, *, fmt: str, interpret: bool = False
):
    """packed: (nblocks, 32) uint8, absmax: (nblocks,) -> (nblocks, 64) fp32."""
    nblocks = packed.shape[0]
    assert packed.shape[1] == BLOCK4 // 2 and nblocks % ROWS4 == 0, packed.shape
    out_t = pl.pallas_call(
        _make_dequant_kernel(_codebook(fmt)),
        grid=(nblocks // ROWS4,),
        in_specs=[
            pl.BlockSpec((BLOCK4 // 2, ROWS4), lambda i: (0, i)),
            pl.BlockSpec((1, ROWS4), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((BLOCK4, ROWS4), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((BLOCK4, nblocks), jnp.float32),
        interpret=interpret,
        name="dequantize_4bit",
    )(packed.T, absmax.astype(jnp.float32).reshape(1, nblocks))
    return out_t.T
