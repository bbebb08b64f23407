"""Pallas TPU kernels for blockwise-int8 quantization (bitsandbytes style).

TPU adaptation (see DESIGN.md §3): bitsandbytes' CUDA kernels assign one
thread per element with a per-block reduction in shared memory. On TPU the
natural mapping is one VMEM tile of whole blocks per grid step: the input
is viewed as ``(nblocks, 4096)`` and each grid step loads a
``(ROWS, 4096)`` fp32 tile (128 KiB — comfortably inside the ~16 MiB VMEM
budget together with the int8 output tile), computes per-row absmax on the
VPU and writes the int8 codes. Block size 4096 is a multiple of the VPU
lane width (128), so rows map cleanly onto (8, 128) vregs.

The per-block absmax travels through the kernel as an ``(nblocks, 1)``
column: Mosaic tiles a rank-1 block only in multiples of 128, while a
``(ROWS, 1)`` block of a 2-D array is legal at any ROWS that is a
multiple of 8. The wrappers reshape to and from the ``(nblocks,)`` wire
layout outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK8 = 4096
ROWS = 8  # blocks (rows) per grid step; (8, 4096) fp32 = 128 KiB VMEM


def _quantize_kernel(x_ref, q_ref, absmax_ref):
    x = x_ref[...].astype(jnp.float32)                      # (ROWS, BLOCK8)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)    # (ROWS, 1)
    scale = jnp.where(absmax > 0.0, 127.0 / absmax, 0.0)
    q = jnp.clip(jnp.round(x * scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int32).astype(jnp.int8)
    absmax_ref[...] = absmax


def _dequantize_kernel(q_ref, absmax_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)                      # (ROWS, BLOCK8)
    scale = absmax_ref[...] / 127.0                         # (ROWS, 1)
    out_ref[...] = q * scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_blockwise8_pallas(x2d: jnp.ndarray, *, interpret: bool = False):
    """x2d: (nblocks, BLOCK8) float; nblocks must be a multiple of ROWS."""
    nblocks = x2d.shape[0]
    assert x2d.shape[1] == BLOCK8 and nblocks % ROWS == 0, x2d.shape
    grid = (nblocks // ROWS,)
    q, absmax = pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, BLOCK8), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((ROWS, BLOCK8), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, BLOCK8), jnp.int8),
            jax.ShapeDtypeStruct((nblocks, 1), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_blockwise8",
    )(x2d)
    return q, absmax.reshape(nblocks)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_blockwise8_pallas(q: jnp.ndarray, absmax: jnp.ndarray, *, interpret: bool = False):
    nblocks = q.shape[0]
    assert q.shape[1] == BLOCK8 and nblocks % ROWS == 0, q.shape
    grid = (nblocks // ROWS,)
    return pl.pallas_call(
        _dequantize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, BLOCK8), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, BLOCK8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, BLOCK8), jnp.float32),
        interpret=interpret,
        name="dequantize_blockwise8",
    )(q, absmax.astype(jnp.float32).reshape(nblocks, 1))
