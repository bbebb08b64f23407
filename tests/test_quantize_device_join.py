"""The batched quantize path builds each slice of a format group's
joined layout on the device, from the tensors where they are:

* its codes and absmaxes are bitwise-identical to quantizing each
  tensor alone, with a group spread over several slices, tensors that
  cross slice boundaries or are smaller than one block, a transposed
  NumPy array, a bfloat16 leaf, and host and device arrays in one
  message;
* traced, a device-resident message moves nothing up and only its
  codes and absmaxes down, opens no ``host.pack`` span, and reports its
  input as ``resident_bytes``; a host message reports it as
  ``uploaded_bytes`` and sends up exactly its leaves;
* the quantize stage's eligibility check reads a device array's dtype
  and size without copying it to the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantization as qz
from repro.core.pipeline import _is_quantizable
from repro.obs import trace as obs_trace

#: eight int8 blocks (the int8 kernel's grid rows), 512 nf4 blocks
SLICE = 8 * 4096


def _message(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {
        # crosses two slice boundaries and ends inside a block
        "long": rng.standard_normal(3 * SLICE + 777).astype(np.float32),
        "tiny": rng.standard_normal(100).astype(np.float32),
        "transposed": rng.standard_normal((301, 257)).astype(np.float32).T,
        "on_device": jnp.asarray(rng.standard_normal((129, 515)), jnp.float32),
        "bf16": jnp.asarray(rng.standard_normal((97, 700)), jnp.bfloat16),
        "bf16_host": rng.standard_normal(5000).astype(jnp.bfloat16),
        "on_device_long": jnp.asarray(rng.standard_normal(SLICE + 5), jnp.float32),
    }


@pytest.mark.parametrize("fmt", ["blockwise8", "nf4"])
def test_sliced_device_join_is_bitwise_per_tensor_quantize(fmt, monkeypatch):
    monkeypatch.setattr(qz, "GROUP_SLICE_ELEMS", SLICE)
    msg = _message(11)
    assert not msg["transposed"].flags.c_contiguous
    out = qz.quantize_batch(msg, {k: fmt for k in msg})
    for name, value in msg.items():
        solo = qz.quantize(np.asarray(value), fmt)
        got = out[name]
        assert isinstance(got.payload, np.ndarray), name
        np.testing.assert_array_equal(got.payload, np.asarray(solo.payload), err_msg=name)
        np.testing.assert_array_equal(got.absmax, np.asarray(solo.absmax), err_msg=name)
        assert got.orig_shape == solo.orig_shape, name
        assert np.dtype(got.orig_dtype) == np.dtype(solo.orig_dtype), name


def _traced_batch(msg: dict, fmt: str) -> tuple[dict, list[dict]]:
    tracer = obs_trace.Tracer()
    with obs_trace.activate(tracer):
        out = qz.quantize_batch(msg, {k: fmt for k in msg})
    return out, [e for e in tracer.chrome_trace()["traceEvents"] if e.get("ph") == "X"]


@pytest.mark.parametrize("placement", ["device", "host"])
@pytest.mark.parametrize("fmt", ["blockwise8", "nf4"])
def test_traced_copies_follow_where_the_message_lives(fmt, placement, monkeypatch):
    monkeypatch.setattr(qz, "GROUP_SLICE_ELEMS", SLICE)
    rng = np.random.default_rng(5)
    host = {"a": rng.standard_normal((3, SLICE // 2 + 9)).astype(np.float32),
            "b": rng.standard_normal(1000).astype(np.float32)}
    msg = host if placement == "host" else {k: jnp.asarray(v) for k, v in host.items()}
    inputs = sum(v.nbytes for v in host.values())
    out, spans = _traced_batch(msg, fmt)

    names = [e["name"] for e in spans]
    assert "host.pack" not in names
    assert names.count("dev.join") >= 2          # several slices, one result join
    results = sum(qt.payload.nbytes + qt.absmax.nbytes for qt in out.values())
    assert sum(e["args"]["nbytes"] for e in spans if e["name"] == "host.d2h") == results
    up = sum(e["args"]["nbytes"] for e in spans if e["name"] == "host.h2d")
    (batch,) = [e for e in spans if e["name"] == "kernel.quantize_batch"]
    if placement == "device":
        assert up == 0
        assert batch["args"]["resident_bytes"] == inputs
        assert batch["args"]["uploaded_bytes"] == 0
    else:
        assert up == inputs
        assert batch["args"]["resident_bytes"] == 0
        assert batch["args"]["uploaded_bytes"] == inputs


def test_is_quantizable_reads_a_device_array_where_it_is():
    """A deleted device array keeps its dtype and shape but has no data
    to copy: any host conversion would raise."""
    x = jnp.ones((64, 64), jnp.float32)
    i = jnp.ones((64,), jnp.int32)
    jax.block_until_ready((x, i))
    x.delete()
    i.delete()
    with pytest.raises(RuntimeError):
        np.asarray(x)
    assert _is_quantizable(x, 4096)
    assert not _is_quantizable(x, 4097)
    assert not _is_quantizable(i, 0)
