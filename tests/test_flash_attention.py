"""Flash-attention Pallas kernel vs pure-jnp oracle: shape/dtype/GQA/mask

sweeps in interpret mode (the compiled path is TPU-only).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas


def _qkv(B, H, KV, S, hd, dtype=jnp.float32, seed=0, sk=None):
    rng = np.random.default_rng(seed)
    sk = sk or S
    q = jnp.asarray(rng.standard_normal((B, H, S, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, KV, sk, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, KV, sk, hd)), dtype)
    return q, k, v


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (4, 1)])
@pytest.mark.parametrize("S", [128, 256])
@pytest.mark.parametrize("hd", [64, 128])
def test_flash_matches_oracle_causal(H, KV, S, hd):
    q, k, v = _qkv(2, H, KV, S, hd, seed=S + hd + H)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(dtype):
    q, k, v = _qkv(1, 2, 2, 128, 64, dtype=dtype, seed=7)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    assert out.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("window", [32, 64])
def test_flash_sliding_window(window):
    q, k, v = _qkv(1, 2, 1, 256, 64, seed=11)
    out = flash_attention_pallas(
        q, k, v, causal=True, window=window, block_q=64, block_k=64, interpret=True
    )
    want = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_flash_non_causal():
    q, k, v = _qkv(1, 2, 2, 128, 64, seed=13)
    out = flash_attention_pallas(q, k, v, causal=False, block_q=64, block_k=64, interpret=True)
    want = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_flash_cross_attention_lengths():
    """Sq != Sk (decoder prompt vs cache)."""
    q, k, v = _qkv(1, 2, 2, 64, 64, seed=17, sk=256)
    out = flash_attention_pallas(q, k, v, causal=False, block_q=64, block_k=64, interpret=True)
    want = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("sq,sk,causal", [(100, 100, True), (130, 130, True),
                                          (40, 200, False)])
def test_flash_pads_lengths_that_do_not_tile(sq, sk, causal):
    """Lengths off the block grid run the kernel: queries and keys are
    padded up to the blocks and the padded keys masked out."""
    q, k, v = _qkv(1, 2, 2, sq, 64, seed=sq + sk, sk=sk)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=64, block_k=64,
                                 interpret=True)
    assert out.shape == q.shape
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("S,window", [(128, None), (100, None), (128, 48)])
def test_flash_gradient_matches_oracle(S, window):
    """The custom VJP (backward through the reference attention) gives
    the oracle's gradients for q, k and v."""
    q, k, v = _qkv(1, 4, 2, S, 64, seed=S + 29)
    g = jnp.asarray(np.random.default_rng(31).standard_normal(q.shape), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * g)

    flash = loss(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, window=window, block_q=64, block_k=64, interpret=True))
    oracle = loss(lambda q, k, v: ref.attention(q, k, v, causal=True, window=window))
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)
