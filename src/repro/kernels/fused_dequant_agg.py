"""Fused dequantize + weighted-accumulate Pallas kernel (server FedAvg).

Beyond-paper optimization (DESIGN.md §7): the paper dequantizes each
client's Task Result to fp32 *before* aggregation, so the server briefly
holds K fp32 copies. This kernel aggregates **directly from the int8
payloads**: each grid step loads the (K, ROWS, 4096) int8 tile of all K
clients (K * 32 KiB — tiny), folds the per-block absmax scales and FedAvg
weights into one (ROWS, 1) scale column per client and sums the K scaled
tiles on the VPU. Per-block absmax columns are ``(.., ROWS, 1)`` blocks
and the weights ride in SMEM, the layouts Mosaic tiles at ROWS = 8.
Server-side peak memory drops from K x fp32-model to 1 x fp32-model, and
the dequantize pass fuses with the reduce.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK8 = 4096
ROWS = 8


def _agg_kernel(q_ref, absmax_ref, w_ref, out_ref):
    # K is small and static: an unrolled chain of VPU multiply-adds, one
    # (R, B) int8 tile per client, each scaled by absmax_k / 127 * w_k
    acc = None
    for k in range(q_ref.shape[0]):
        scale = absmax_ref[k] / 127.0 * w_ref[0, k]          # (R, 1)
        term = q_ref[k].astype(jnp.float32) * scale          # (R, B)
        acc = term if acc is None else acc + term
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_accumulate8_pallas(
    qs: jnp.ndarray, absmaxes: jnp.ndarray, weights: jnp.ndarray, *, interpret: bool = False
):
    """qs: (K, nblocks, 4096) int8; absmaxes: (K, nblocks); weights: (K,).

    Returns (nblocks, 4096) fp32 = sum_k weights[k] * dequant(qs[k]).
    """
    K, nblocks, b = qs.shape
    assert b == BLOCK8 and nblocks % ROWS == 0, qs.shape
    grid = (nblocks // ROWS,)
    return pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, ROWS, BLOCK8), lambda i: (0, i, 0)),
            pl.BlockSpec((K, ROWS, 1), lambda i: (0, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((ROWS, BLOCK8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, BLOCK8), jnp.float32),
        interpret=interpret,
        name="dequant_accumulate8",
    )(
        qs,
        absmaxes.astype(jnp.float32).reshape(K, nblocks, 1),
        weights.astype(jnp.float32).reshape(1, K),
    )


def _fold_kernel(acc_ref, q_ref, absmax_ref, w_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)                       # (R, B)
    scale = absmax_ref[...] / 127.0                          # (R, 1)
    scale = scale * w_ref[0, 0]                              # fold FedAvg w_k
    out_ref[...] = acc_ref[...] + q * scale


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def dequant_accumulate8_into_pallas(
    acc: jnp.ndarray, q: jnp.ndarray, absmax: jnp.ndarray, weight: jnp.ndarray,
    *, interpret: bool = False
):
    """Streaming fold: ``acc + weight * dequant(q)``, one contribution at
    a time, **into** the running fp32 accumulator.

    ``acc`` is donated and the output aliases it
    (``input_output_aliases={0: 0}``), so the per-item fold of the
    streaming aggregation plane updates the accumulator in place —
    no fp32 temporary of the dequantized contribution, no second
    accumulator allocation per fold. acc: (nblocks, 4096) fp32;
    q: (nblocks, 4096) int8; absmax: (nblocks,); weight: scalar.
    """
    nblocks, b = q.shape
    assert b == BLOCK8 and nblocks % ROWS == 0, q.shape
    assert acc.shape == q.shape, (acc.shape, q.shape)
    grid = (nblocks // ROWS,)
    w = jnp.reshape(weight, (1, 1)).astype(jnp.float32)
    return pl.pallas_call(
        _fold_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, BLOCK8), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, BLOCK8), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((ROWS, BLOCK8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, BLOCK8), jnp.float32),
        input_output_aliases={0: 0},
        interpret=interpret,
        name="dequant_accumulate8_into",
    )(acc, q, absmax.astype(jnp.float32).reshape(nblocks, 1), w)
