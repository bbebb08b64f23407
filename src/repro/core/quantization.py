"""Message quantization codecs — the paper's §II contribution.

A :class:`QuantizedTensor` is the wire representation of one parameter
tensor; :func:`quantize` / :func:`dequantize` convert arrays, and
:func:`quantize_state_dict` / :func:`dequantize_state_dict` convert whole
FL messages. Formats and their metadata layout follow bitsandbytes as
used by NVFlare 2.6 (paper Table II):

=============  ==========  =====================  ====================
format         payload     meta                   fp32 size
=============  ==========  =====================  ====================
fp16 / bf16    16-bit      —                      50.00 %
blockwise8     int8        fp32 absmax / 4096     25.03 %
fp4 / nf4      4-bit x2/B  fp32 absmax / 64       14.06 %
=============  ==========  =====================  ====================

Compute is delegated to ``repro.kernels.ops`` (Pallas on TPU, jnp ref on
CPU). Training/aggregation always run at original precision — codecs are
applied only at the four filter points (see ``repro.core.filters``).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.obs import trace as obs_trace

FORMATS = ("fp32", "fp16", "bf16", "blockwise8", "fp4", "nf4")
_CAST = {"fp16": jnp.float16, "bf16": jnp.bfloat16}
_BLOCKED = {"blockwise8", "fp4", "nf4"}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """Wire format for one tensor: payload + quantization metadata."""

    payload: jnp.ndarray                 # int8 / uint8(packed) / fp16 / bf16 / fp32
    absmax: Optional[jnp.ndarray]        # per-block absmax (blocked formats)
    fmt: str
    orig_shape: tuple[int, ...]
    orig_dtype: Any

    # -- pytree protocol (so messages can cross jit/shard_map) -------------
    def tree_flatten(self):
        children = (self.payload, self.absmax)
        aux = (self.fmt, self.orig_shape, str(np.dtype(self.orig_dtype)))
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        fmt, shape, dtype = aux
        return cls(children[0], children[1], fmt, tuple(shape), np.dtype(dtype))

    # -- accounting (paper Table II) ---------------------------------------
    @property
    def payload_bytes(self) -> int:
        return int(self.payload.size) * np.dtype(self.payload.dtype).itemsize

    @property
    def meta_bytes(self) -> int:
        if self.absmax is None:
            return 0
        return int(self.absmax.size) * np.dtype(self.absmax.dtype).itemsize

    @property
    def total_bytes(self) -> int:
        return self.payload_bytes + self.meta_bytes


def quantize(x: jnp.ndarray, fmt: str) -> QuantizedTensor:
    if fmt not in FORMATS:
        raise ValueError(f"unknown quantization format {fmt!r}; valid: {FORMATS}")
    shape, dtype = tuple(x.shape), x.dtype
    if fmt == "fp32":
        return QuantizedTensor(x.astype(jnp.float32), None, fmt, shape, dtype)
    if fmt in _CAST:
        # direct crop-and-cast (paper §II-D)
        return QuantizedTensor(x.astype(_CAST[fmt]), None, fmt, shape, dtype)
    if fmt == "blockwise8":
        q, absmax = ops.quantize_blockwise8(x)
        return QuantizedTensor(q, absmax, fmt, shape, dtype)
    # fp4 / nf4
    packed, absmax = ops.quantize_4bit(x, fmt)
    return QuantizedTensor(packed, absmax, fmt, shape, dtype)


def dequantize(qt: QuantizedTensor) -> jnp.ndarray:
    fmt = qt.fmt
    if fmt == "fp32" or fmt in _CAST:
        return qt.payload.astype(qt.orig_dtype).reshape(qt.orig_shape)
    if fmt == "blockwise8":
        return ops.dequantize_blockwise8(qt.payload, qt.absmax, qt.orig_shape, qt.orig_dtype)
    return ops.dequantize_4bit(qt.payload, qt.absmax, fmt, qt.orig_shape, qt.orig_dtype)


# ---------------------------------------------------------------------------
# state-dict level (what the FL filters actually transform)
# ---------------------------------------------------------------------------

def quantize_state_dict(sd: Mapping[str, jnp.ndarray], fmt: str) -> dict[str, QuantizedTensor]:
    return {name: quantize(arr, fmt) for name, arr in sd.items()}


_BLOCK_OF = {"blockwise8": 4096, "fp4": 64, "nf4": 64}


#: elements per kernel dispatch when a fused group is larger: a whole
#: model in one dispatch needs its fp32 input plus the kernel's padded
#: working copies on the device at once (~10 GB for a 0.6B-parameter
#: model, most of a 16 GB TPU), while a bounded slice keeps the device
#: footprint O(slice). A multiple of every format's block size times its
#: kernel's grid rows, so slice boundaries never split a block and each
#: slice is padded the same way the whole group would be.
GROUP_SLICE_ELEMS = 1 << 26


def _fused_quantize_group(
    items: Mapping[str, Any], names: list[str], fmt: str
) -> dict[str, QuantizedTensor]:
    """One kernel dispatch per :data:`GROUP_SLICE_ELEMS` of a whole
    format group: every tensor is padded to whole quant blocks (exactly
    the per-tensor wire layout) and laid back to back in one fp32
    buffer, the blocked kernel runs over it slice by slice, and each
    tensor's payload/absmax are row slices of the joined result. Block
    boundaries never span tensors or slices, so the sliced payloads are
    bitwise-identical to quantizing each tensor alone.

    The concat buffer is O(group) *compute scratch* on the sender —
    the same order as the fp32 message the sender already holds, and
    deliberately outside the MemoryMeter, which tracks transmission
    buffers (those stay O(item) under container streaming).

    Traced, each device->host copy is a ``host.d2h`` span and the NumPy
    staging (the joined buffer, the joined results) ``host.pack``."""
    block = _BLOCK_OF[fmt]
    spans: list[tuple[str, Any, int, int]] = []   # name, arr, start, nblocks
    total = 0
    for name in names:
        arr = ops.to_host(items[name])
        nb = int(np.ceil(arr.size / block))
        spans.append((name, arr, total, nb))
        total += nb
    with obs_trace.span("host.pack", "host", nbytes=4 * total * block):
        big = np.zeros(total * block, np.float32)
        for _name, arr, start, _nb in spans:
            flat = np.ascontiguousarray(arr).reshape(-1)
            big[start * block: start * block + flat.size] = flat
    qs, ams = [], []
    for lo in range(0, big.size, GROUP_SLICE_ELEMS):
        part = big[lo:lo + GROUP_SLICE_ELEMS]
        if fmt == "blockwise8":
            q, am = ops.quantize_blockwise8(part)
        else:
            q, am = ops.quantize_4bit(part, fmt)
        qs.append(ops.to_host(q))     # one sync per slice
        ams.append(ops.to_host(am))
    joined = sum(p.nbytes for parts in (qs, ams) if len(parts) > 1 for p in parts)
    with obs_trace.span("host.pack", "host", nbytes=joined):
        q_np = qs[0] if len(qs) == 1 else np.concatenate(qs)
        am_np = ams[0] if len(ams) == 1 else np.concatenate(ams)
    return {
        name: QuantizedTensor(q_np[start:start + nb], am_np[start:start + nb],
                              fmt, tuple(arr.shape), arr.dtype)
        for name, arr, start, nb in spans
    }


def quantize_batch(
    items: Mapping[str, Any], fmt_for: Mapping[str, str]
) -> dict[str, QuantizedTensor]:
    """Whole-message quantization: one kernel dispatch **per format
    group** (all same-format tensors concatenated block-aligned; groups
    past :data:`GROUP_SLICE_ELEMS` dispatch once per slice), one device
    sync per dispatch.

    This is the wire hot path's replacement for per-tensor
    dispatch-then-sync inside the streamer loop: serializing item k
    forced a device sync before item k+1 could even dispatch, so the
    host alternated between Python framing work and kernel waits — at
    LLM layer counts the dispatch overhead dominated the quantization
    compute several times over. ``fmt_for`` maps item name -> format;
    items absent from it pass through untouched. Results are
    bitwise-identical to calling :func:`quantize` per item — only the
    dispatch schedule changes (asserted by the golden-bytes suite).
    """
    out: dict[str, QuantizedTensor] = {}
    groups: dict[str, list[str]] = {}
    for name, value in items.items():
        fmt = fmt_for.get(name)
        if fmt is None:
            continue
        if fmt in _BLOCK_OF:
            groups.setdefault(fmt, []).append(name)
        else:  # fp32/fp16/bf16 casts: cheap host-side per-tensor work
            out[name] = quantize(np.asarray(value), fmt)
    tr = obs_trace.ACTIVE
    for fmt, names in groups.items():
        if tr is None:
            out.update(_fused_quantize_group(items, names, fmt))
        else:
            with tr.span("kernel.quantize_batch", "kernel", fmt=fmt,
                         items=len(names)):
                out.update(_fused_quantize_group(items, names, fmt))
    ops.block_until_ready([(qt.payload, qt.absmax) for qt in out.values()])
    return out


def _fused_dequantize_group(
    items: Mapping[str, Any], names: list[str], fmt: str
) -> dict[str, np.ndarray]:
    """Inverse of :func:`_fused_quantize_group`: payload/absmax rows of
    every same-format tensor are laid back to back and the blocked
    kernel runs over them one :data:`GROUP_SLICE_ELEMS` slice at a time.
    Block boundaries never span tensors, so the per-tensor slices are
    element-wise identical to dequantizing each tensor alone.

    Traced, as in :func:`_fused_quantize_group`: ``host.d2h`` per
    device->host copy, ``host.pack`` around the joins."""
    block = _BLOCK_OF[fmt]
    spans: list[tuple[str, QuantizedTensor, int, int]] = []  # name, qt, start, nblocks
    total = 0
    for name in names:
        qt = items[name]
        nb = int(qt.absmax.shape[0])
        spans.append((name, qt, total, nb))
        total += nb
    qs = [ops.to_host(qt.payload) for _n, qt, _s, _nb in spans]
    ams = [ops.to_host(qt.absmax) for _n, qt, _s, _nb in spans]
    with obs_trace.span("host.pack", "host", nbytes=sum(p.nbytes for p in qs + ams)):
        q_cat = np.concatenate(qs)
        am_cat = np.concatenate(ams)
    rows = GROUP_SLICE_ELEMS // block
    parts = []
    for lo in range(0, total, rows):
        n = min(rows, total - lo)
        if fmt == "blockwise8":
            flat = ops.dequantize_blockwise8(q_cat[lo:lo + n], am_cat[lo:lo + n],
                                             (n * block,), np.float32)
        else:
            flat = ops.dequantize_4bit(q_cat[lo:lo + n], am_cat[lo:lo + n], fmt,
                                       (n * block,), np.float32)
        parts.append(ops.to_host(flat))   # one sync per slice
    joined = sum(p.nbytes for p in parts) if len(parts) > 1 else 0
    with obs_trace.span("host.pack", "host", nbytes=joined):
        flat_np = parts[0] if len(parts) == 1 else np.concatenate(parts)
    out: dict[str, np.ndarray] = {}
    for name, qt, start, _nb in spans:
        size = int(np.prod(qt.orig_shape)) if qt.orig_shape else 1
        out[name] = (
            flat_np[start * block: start * block + size]
            .reshape(qt.orig_shape)
            .astype(np.dtype(qt.orig_dtype), copy=False)
        )
    return out


def dequantize_batch(items: Mapping[str, Any]) -> dict[str, Any]:
    """Whole-message dequantization: one kernel dispatch **per format
    group** (per :data:`GROUP_SLICE_ELEMS` slice of it), one device sync
    per dispatch — the receive-side mirror of
    :func:`quantize_batch`. Items that are not :class:`QuantizedTensor`
    (dense arrays, other wire kinds) pass through untouched; cast
    formats (fp32/fp16/bf16) are cheap per-tensor host work. Results
    are bitwise-identical to calling :func:`dequantize` per item —
    only the dispatch schedule changes."""
    out: dict[str, Any] = {}
    groups: dict[str, list[str]] = {}
    for name, value in items.items():
        if isinstance(value, QuantizedTensor) and value.fmt in _BLOCK_OF:
            groups.setdefault(value.fmt, []).append(name)
            out[name] = None   # placeholder keeps payload ordering stable
        elif isinstance(value, QuantizedTensor):
            out[name] = np.asarray(dequantize(value))
        else:
            out[name] = value
    tr = obs_trace.ACTIVE
    for fmt, names in groups.items():
        if tr is None:
            out.update(_fused_dequantize_group(items, names, fmt))
        else:
            with tr.span("kernel.dequantize_batch", "kernel", fmt=fmt,
                         items=len(names)):
                out.update(_fused_dequantize_group(items, names, fmt))
    return out


def dequantize_state_dict(qsd: Mapping[str, QuantizedTensor]) -> dict[str, jnp.ndarray]:
    return {name: dequantize(qt) for name, qt in qsd.items()}


def message_size_report(sd: Mapping[str, jnp.ndarray], fmt: str) -> dict[str, float]:
    """Byte accounting for one message under ``fmt`` **without** running

    the quantizer — pure arithmetic over shapes, used by the Table II
    benchmark and by the bandwidth planner. Matches the padded sizes the
    real codecs produce to within block-padding (<1 block per tensor).
    """
    mb = 1024.0 * 1024.0
    n_params = sum(int(np.prod(a.shape)) for a in sd.values())
    fp32_bytes = 4.0 * n_params
    if fmt == "fp32":
        payload, meta = fp32_bytes, 0.0
    elif fmt in ("fp16", "bf16"):
        payload, meta = 2.0 * n_params, 0.0
    elif fmt == "blockwise8":
        payload = 1.0 * n_params
        # absmax per 4096-block + bitsandbytes' per-tensor 256-entry fp32
        # dynamic code map (1 KiB) — included so Table II reproduces the
        # paper's 1.54 MB meta for the 147-layer Llama-3.2-1B dict.
        meta = 4.0 * sum(int(np.ceil(np.prod(a.shape) / 4096)) for a in sd.values())
        meta += 1024.0 * len(sd)
    elif fmt in ("fp4", "nf4"):
        payload = 0.5 * n_params
        meta = 4.0 * sum(int(np.ceil(np.prod(a.shape) / 64)) for a in sd.values())
    else:
        raise ValueError(fmt)
    return {
        "format": fmt,
        "model_mb": payload / mb,
        "meta_mb": meta / mb,
        "total_mb": (payload + meta) / mb,
        "fp32_pct": 100.0 * (payload + meta) / fp32_bytes,
    }
