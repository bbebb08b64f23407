"""Fused sLSTM time-scan Pallas kernel (§Perf pair 2, final iteration).

The HLO form of the sLSTM recurrence round-trips every timestep's state
through HBM (4096 tiny fusions per layer — the dominant memory term of
xlstm-125m train_4k even after input-projection hoisting). The xLSTM
paper fuses the whole recurrence into one CUDA kernel; the TPU analogue
is this Pallas kernel:

* grid = (batch, seq_chunks) with the seq dimension **sequential**; the
  (c, n, h, m) state lives in VMEM scratch across grid steps (reset at
  chunk 0 of each batch row).
* each grid step streams one (chunk x 4 x D) slice of the hoisted gate
  pre-activations from HBM, runs `chunk` recurrence steps entirely in
  VMEM/VREGs (per-head (hd x hd) recurrent matmuls on the MXU), and
  streams the (chunk x D) hidden states out.

HBM traffic per layer drops from O(S x state x passes) round-trips to a
single gx read + h write: ~(4+1) x S x D x 4 B.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 256


def _kernel(gx_ref, r_ref, h_out_ref, c_s, n_s, h_s, m_s, *, chunk: int, H: int, hd: int):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _reset():
        c_s[...] = jnp.zeros_like(c_s)
        n_s[...] = jnp.zeros_like(n_s)
        h_s[...] = jnp.zeros_like(h_s)
        m_s[...] = jnp.full_like(m_s, -1e30)

    gx = gx_ref[0].astype(jnp.float32)          # (chunk, 4, H*hd)
    r = r_ref[...].astype(jnp.float32)          # (4, H, hd, hd)

    def step(t, carry):
        c, n, h, m = carry                      # each (H, hd)
        g_t = gx[t].reshape(4, H, hd)
        # recurrent part: per-head (1, hd_in) @ (hd_in, 4*hd_out) on the MXU
        rr = r.transpose(1, 2, 0, 3).reshape(H, hd, 4 * hd)  # (H, hd_in, gate*hd_out)
        gh = jax.lax.dot_general(
            h[:, None, :], rr,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                        # (H, 1, 4*hd)
        gh = gh.reshape(H, 4, hd).transpose(1, 0, 2)  # (4, H, hd)
        z_in, i_in, f_in, o_in = g_t[0] + gh[0], g_t[1] + gh[1], g_t[2] + gh[2], g_t[3] + gh[3]
        z = jnp.tanh(z_in)
        o = jax.nn.sigmoid(o_in)
        logi = i_in
        logf = jax.nn.log_sigmoid(f_in)
        m_new = jnp.maximum(logf + m, logi)
        i_s = jnp.exp(logi - m_new)
        f_s = jnp.exp(logf + m - m_new)
        c_new = f_s * c + i_s * z
        n_new = f_s * n + i_s
        h_new = o * c_new / jnp.maximum(n_new, 1e-6)
        h_out_ref[0, t] = h_new.reshape(H * hd).astype(h_out_ref.dtype)
        return c_new, n_new, h_new, m_new

    init = (c_s[...], n_s[...], h_s[...], m_s[...])
    c, n, h, m = jax.lax.fori_loop(0, chunk, step, init)
    c_s[...] = c
    n_s[...] = n
    h_s[...] = h
    m_s[...] = m


@functools.partial(jax.jit, static_argnames=("num_heads", "chunk", "interpret"))
def slstm_scan_pallas(
    gx: jnp.ndarray,
    r: jnp.ndarray,
    *,
    num_heads: int,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jnp.ndarray:
    """gx: (B, S, 4, D) hoisted gate pre-activations (gate order z,i,f,o);

    r: (4, H, hd, hd) recurrent weights. Returns hidden states (B, S, D)
    fp32. S % chunk == 0.
    """
    Bsz, S, four, D = gx.shape
    assert four == 4 and S % chunk == 0, gx.shape
    H = num_heads
    hd = D // H
    grid = (Bsz, S // chunk)
    kernel = functools.partial(_kernel, chunk=chunk, H=H, hd=hd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 4, D), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((4, H, hd, hd), lambda b, s: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, D), lambda b, s: (b, s, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, S, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((H, hd), jnp.float32),  # c
            pltpu.VMEM((H, hd), jnp.float32),  # n
            pltpu.VMEM((H, hd), jnp.float32),  # h
            pltpu.VMEM((H, hd), jnp.float32),  # m
        ],
        interpret=interpret,
        name="slstm_scan",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(gx, r)
