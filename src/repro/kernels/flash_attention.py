"""Flash-attention Pallas TPU kernel (perf iteration 2 of §Perf pair 1).

The baseline HLO materializes the (S x S) fp32 score tensor through
mask/softmax — the dominant HBM-traffic term for every attention arch in
the dry-run roofline (EXPERIMENTS.md §Roofline). This kernel computes
attention with **online softmax over K/V tiles held in VMEM**: HBM
traffic drops from O(S^2) scores to O(S) q/k/v/out streams.

TPU adaptation: one grid step = one (batch, q-head, q-block). The
BlockSpec pins the q tile (block_q x hd) and the *whole* K/V stripe of
the matching KV head (S x hd — 8 MiB at S=32k, hd=128, bf16; within the
~16 MiB VMEM budget) and an inner ``fori_loop`` walks K/V in block_k
chunks carrying (m, l, acc) — the standard flash recurrence, with MXU
matmuls at (block_q x hd) x (hd x block_k). GQA maps q head h to KV head
h * KV // H in the index map. Causal and sliding-window masks are index
arithmetic, not materialized tensors.

Sequence lengths that do not tile the blocks are zero-padded up to them;
padded keys are masked out by index (``kv_len``) and padded query rows
are sliced off, so any length runs the kernel.

Gradients: :func:`flash_attention_pallas` is a ``jax.custom_vjp`` whose
backward recomputes attention through the reference oracle
(``repro.kernels.ref.attention``) and differentiates that — O(S^2)
memory in the backward pass, exact forward semantics. A Pallas backward
kernel is future work.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import ref

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
            window: Optional[int], kv_len: Optional[int], nk: int, block_q: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)                    # (block_q, hd)
    hd = q.shape[-1]
    scale = 1.0 / np.sqrt(hd)
    q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, carry):
        m, l, acc = carry
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, 0, pl.ds(start, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(start, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                           # (block_q, block_k)
        k_idx = start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if kv_len is not None:
            mask = mask & (k_idx < kv_len)
        if causal:
            mask = mask & (k_idx <= q_idx)
        if window is not None:
            mask = mask & (q_idx - k_idx < window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _pad_seq(x: jnp.ndarray, block: int) -> jnp.ndarray:
    s = x.shape[2]
    padded = -(-s // block) * block
    if padded == s:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, padded - s), (0, 0)))


def _flash_forward(q, k, v, causal, window, block_q, block_k, interpret):
    Bsz, H, sq, hd = q.shape
    KV, sk = k.shape[1], k.shape[2]
    assert H % KV == 0, (q.shape, k.shape)
    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v, block_k)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    group = H // KV
    kernel = functools.partial(
        _kernel, block_k=block_k, causal=causal, window=window,
        kv_len=sk if sk_p != sk else None, nk=sk_p // block_k, block_q=block_q,
    )
    out = pl.pallas_call(
        kernel,
        grid=(Bsz, H, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, sk_p, hd), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, sk_p, hd), lambda b, h, i: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, sq_p, hd), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out[:, :, :sq] if sq_p != sq else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, window, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out = _flash_forward(q, k, v, causal, window, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, interpret, residuals, g):
    q, k, v = residuals
    _, vjp = jax.vjp(
        lambda q, k, v: ref.attention(q, k, v, causal=causal, window=window), q, k, v
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd); H % KV == 0.

    Returns (B, H, Sq, hd) in q.dtype. Any Sq / Sk: lengths that do not
    tile ``block_q`` / ``block_k`` are padded inside and masked.
    Differentiable (backward through the reference attention).
    """
    return _flash(q, k, v, causal, window, block_q, block_k, interpret)
