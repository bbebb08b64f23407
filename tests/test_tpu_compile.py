"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

Nothing here runs on a chip: the TPU compiler that ships with JAX
compiles for a v5e that is described (``topologies.get_topology_desc``)
and not attached, and refuses what the chip would refuse — block shapes
Mosaic cannot tile, primitives it cannot lower, programs that do not fit
the device's memory. Every program must keep its Mosaic kernel
(``tpu_custom_call``): an interpret-mode or jnp fallback would not.

Sizes are qwen1.5-0.5b's (hf:Qwen/Qwen1.5-0.5B): the 151936 x 1024
embedding (37,984 int8 blocks, 2,430,976 nf4 blocks), attention at
batch 4 x 16 heads x seq 512 x head dim 64, and the full-width local
train step. The topology is described inside a module fixture — never
at import — and the tests skip where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

VOCAB, D_MODEL = 151936, 1024
N_EMBED = VOCAB * D_MODEL
NB8 = N_EMBED // ops.BLOCK8            # 37,984
NB8_ROWS = -(-NB8 // ops.ROWS) * ops.ROWS
NB4 = N_EMBED // ops.BLOCK4
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def chip(topo):
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_with_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _int8_ops(chip):
    from repro.kernels.fused_dequant_agg import (
        dequant_accumulate8_into_pallas,
        dequant_accumulate8_pallas,
    )
    from repro.kernels.quant_blockwise8 import (
        dequantize_blockwise8_pallas,
        quantize_blockwise8_pallas,
    )

    f32 = jnp.float32
    return {
        "quantize": lambda: quantize_blockwise8_pallas.lower(
            chip((NB8_ROWS, ops.BLOCK8), f32)),
        "dequantize": lambda: dequantize_blockwise8_pallas.lower(
            chip((NB8_ROWS, ops.BLOCK8), jnp.int8), chip((NB8_ROWS,), f32)),
        "streaming_fold": lambda: dequant_accumulate8_into_pallas.lower(
            chip((NB8_ROWS, ops.BLOCK8), f32), chip((NB8_ROWS, ops.BLOCK8), jnp.int8),
            chip((NB8_ROWS,), f32), chip((), f32)),
        "k_way_fold": lambda: dequant_accumulate8_pallas.lower(
            chip((2, NB8_ROWS, ops.BLOCK8), jnp.int8), chip((2, NB8_ROWS), f32),
            chip((2,), f32)),
    }


@pytest.mark.parametrize("op", ["quantize", "dequantize", "streaming_fold", "k_way_fold"])
def test_int8_kernels_compile_for_v5e(chip, op):
    _compile_with_kernel(_int8_ops(chip)[op]())


@pytest.mark.parametrize("op", ["quantize", "dequantize"])
@pytest.mark.parametrize("fmt", ["nf4", "fp4"])
def test_4bit_kernels_compile_for_v5e(chip, op, fmt):
    from repro.kernels.quant_nf4 import dequantize_4bit_pallas, quantize_4bit_pallas

    if op == "quantize":
        lowered = quantize_4bit_pallas.lower(chip((NB4, ops.BLOCK4), jnp.float32), fmt=fmt)
    else:
        lowered = dequantize_4bit_pallas.lower(
            chip((NB4, ops.BLOCK4 // 2), jnp.uint8), chip((NB4,), jnp.float32), fmt=fmt)
    _compile_with_kernel(lowered)


@pytest.mark.parametrize("fmt", ["blockwise8", "nf4"])
def test_group_slice_quantize_fits_in_bounded_memory(chip, fmt):
    """A fused quantize group dispatches one GROUP_SLICE_ELEMS slice at a
    time; one slice's whole op (pad, kernel, slice back) needs well
    under 2 GB of device memory, whatever the model size."""
    from repro.core.quantization import GROUP_SLICE_ELEMS

    x = chip((GROUP_SLICE_ELEMS,), jnp.float32)
    if fmt == "blockwise8":
        lowered = ops._pallas_q8_full.lower(x, interpret=False)
    else:
        lowered = ops._pallas_q4_full.lower(x, fmt=fmt, interpret=False)
    ma = _compile_with_kernel(lowered).memory_analysis()
    total = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
    assert total < 2 * 10**9, total


@pytest.mark.parametrize("placement", ["resident", "uploaded"])
def test_group_slice_join_fits_in_bounded_memory(chip, placement):
    """A fused quantize group's slice is assembled on the device: here
    the third slice of a group that opens with the embedding and goes
    on into qwen's stacked MLP weight (24 x 1024 x 2816), taken from the
    whole device arrays (an uplink) or from the uploaded pieces (a
    downlink). Its output and scratch stay O(slice)."""
    from repro.core.quantization import GROUP_SLICE_ELEMS, _join_slice

    lo = 2 * GROUP_SLICE_ELEMS
    pad = -N_EMBED % ops.BLOCK8
    at = N_EMBED + pad - lo
    n_mlp = GROUP_SLICE_ELEMS - at
    if placement == "resident":
        pieces = (chip((VOCAB, D_MODEL), jnp.float32), chip((24, D_MODEL, 2816), jnp.float32))
        cuts = ((0, lo, N_EMBED), (at, 0, n_mlp))
    else:
        pieces = (chip((N_EMBED - lo,), jnp.float32), chip((n_mlp,), jnp.float32))
        cuts = ((0, 0, N_EMBED - lo), (at, 0, n_mlp))
    ma = _join_slice.lower(pieces, cuts, GROUP_SLICE_ELEMS).compile().memory_analysis()
    assert ma.output_size_in_bytes == 4 * GROUP_SLICE_ELEMS
    assert ma.output_size_in_bytes + ma.temp_size_in_bytes < 4 * 4 * GROUP_SLICE_ELEMS


@pytest.mark.parametrize("mode", ["forward", "value_and_grad"])
def test_flash_attention_compiles_for_v5e(chip, mode):
    from repro.kernels.flash_attention import flash_attention_pallas

    qkv = [chip((4, 16, 512, 64), jnp.float32)] * 3
    if mode == "forward":
        fn = flash_attention_pallas
    else:
        fn = jax.value_and_grad(
            lambda q, k, v: flash_attention_pallas(q, k, v).sum(), argnums=(0, 1, 2))
    _compile_with_kernel(jax.jit(fn).lower(*qkv))


def test_full_width_local_step_fits_one_v5e(chip):
    """qwen1.5-0.5b's local AdamW step at batch 4 x seq 512, attention on
    the flash kernel, fits one chip's HBM with params and optimizer
    state donated (16.1 GB without donation)."""
    from repro.configs import get_config
    from repro.fl.job import _jit_local_step
    from repro.models import create_model
    from repro.optim import adamw_init

    model = create_model(get_config("qwen1.5-0.5b"))

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) \
        == 619_570_176
    opt = on_chip(jax.eval_shape(adamw_init, params))
    batch = {k: chip((4, 512), jnp.int32) for k in ("tokens", "labels")}
    with ops.backend("pallas"):
        lowered = _jit_local_step(model, 3e-3).lower(params, opt, batch)
    ma = _compile_with_kernel(lowered).memory_analysis()
    assert ma.alias_size_in_bytes > 0
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, total
