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
import functools
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


#: elements per kernel dispatch of a fused group. Each slice of the
#: group's joined, block-padded layout is built on the device just
#: before its dispatch, so the encoder's device scratch is one slice of
#: float32 input, not the whole group: a whole model joined at once
#: needs its fp32 input plus the kernel's padded working copies (~10 GB
#: for a 0.6B-parameter model, most of a 16 GB TPU). A multiple of
#: every format's block size times its kernel's grid rows, so slice
#: boundaries never split a block and each slice is padded the same way
#: the whole group would be.
GROUP_SLICE_ELEMS = 1 << 26


@functools.partial(jax.jit, static_argnames=("cuts", "size"))
def _join_slice(pieces, cuts, size):
    """One ``size``-element float32 slice of a group's joined layout:
    for each ``(at, a, b)`` of ``cuts``, elements ``[a, b)`` of the
    matching flattened piece land at ``at``; the rest is zero padding."""
    parts, pos = [], 0
    for x, (at, a, b) in zip(pieces, cuts):
        if at > pos:
            parts.append(jnp.zeros(at - pos, jnp.float32))
        if x.ndim > 1:
            # cut the leading rows the slice takes first: flattening the
            # whole array would relayout all of it on the device
            row = int(np.prod(x.shape[1:]))
            r0 = a // row
            x, a, b = x[r0:-(-b // row)], a - r0 * row, b - r0 * row
        parts.append(x.reshape(-1)[a:b].astype(jnp.float32))
        pos = at + b - a
    if pos < size:
        parts.append(jnp.zeros(size - pos, jnp.float32))
    return jnp.concatenate(parts)


def _fused_quantize_group(
    values: Mapping[str, Any], fmt: str
) -> dict[str, QuantizedTensor]:
    """One kernel dispatch per :data:`GROUP_SLICE_ELEMS` of a whole
    format group. The group's layout (every tensor padded to whole quant
    blocks, exactly the per-tensor wire layout, laid back to back) is
    worked out from shapes alone; each slice of it is built on the
    device from the pieces of the tensors that fall in it: a device
    array is sliced where it is, a host array sends up only the
    contiguous views the slice needs, and the padding and the float32
    cast are device work. Each slice's codes and absmaxes stay on the
    device, are joined there, and come to the host once per group; each
    tensor's payload/absmax are row slices of the joined result. Block
    boundaries never span tensors or slices, so the sliced payloads are
    bitwise-identical to quantizing each tensor alone.

    Traced, ``dev.join`` times each slice's assembly (with ``host.h2d``
    around the upload of its host pieces, ``nbytes``) and the join of
    the results; ``host.d2h`` times the group's two copies down."""
    block = _BLOCK_OF[fmt]
    spans: list[tuple[str, Any, int, int]] = []   # name, value, first elem, nblocks
    total = 0
    for name, value in values.items():
        nb = -(-int(value.size) // block)
        spans.append((name, value, total * block, nb))
        total += nb
    flats: dict[str, np.ndarray] = {}   # host tensors, flattened once
    qs, ams = [], []
    for lo in range(0, total * block, GROUP_SLICE_ELEMS):
        hi = min(lo + GROUP_SLICE_ELEMS, total * block)
        pieces, cuts, host = [], [], []
        for name, value, first, _nb in spans:
            a, b = max(lo, first), min(hi, first + int(value.size))
            if a >= b:
                continue
            if isinstance(value, jax.Array):
                pieces.append(value)
                cuts.append((a - lo, a - first, b - first))
            else:
                if name not in flats:
                    flats[name] = value.reshape(-1)
                host.append(len(pieces))
                pieces.append(flats[name][a - first:b - first])
                cuts.append((a - lo, 0, b - a))
        with obs_trace.span("dev.join", "dev"):
            if host:
                up = [pieces[i] for i in host]
                with obs_trace.span("host.h2d", "host",
                                    nbytes=sum(p.nbytes for p in up)):
                    up = jax.device_put(up)
                for i, x in zip(host, up):
                    pieces[i] = x
            part = _join_slice(tuple(pieces), tuple(cuts), hi - lo)
        if fmt == "blockwise8":
            q, am = ops.quantize_blockwise8(part)
        else:
            q, am = ops.quantize_4bit(part, fmt)
        qs.append(q)
        ams.append(am)
    if len(qs) > 1:
        with obs_trace.span("dev.join", "dev"):
            qs, ams = [jnp.concatenate(qs)], [jnp.concatenate(ams)]
    q_np, am_np = ops.to_host(qs[0]), ops.to_host(ams[0])
    return {
        name: QuantizedTensor(q_np[first // block:first // block + nb],
                              am_np[first // block:first // block + nb],
                              fmt, tuple(value.shape), np.dtype(value.dtype))
        for name, value, first, nb in spans
    }


def quantize_batch(
    items: Mapping[str, Any], fmt_for: Mapping[str, str]
) -> dict[str, QuantizedTensor]:
    """Whole-message quantization: one kernel dispatch **per format
    group** (all same-format tensors joined block-aligned on the device;
    groups past :data:`GROUP_SLICE_ELEMS` dispatch once per slice), one
    copy of the group's codes and absmaxes to the host.

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
    groups: dict[str, dict[str, Any]] = {}
    for name, value in items.items():
        fmt = fmt_for.get(name)
        if fmt is None:
            continue
        if fmt in _BLOCK_OF:
            if not isinstance(value, (jax.Array, np.ndarray)):
                value = np.asarray(value)
            groups.setdefault(fmt, {})[name] = value
        else:  # fp32/fp16/bf16 casts: cheap host-side per-tensor work
            out[name] = quantize(np.asarray(value), fmt)
    tr = obs_trace.ACTIVE
    for fmt, values in groups.items():
        if tr is None:
            out.update(_fused_quantize_group(values, fmt))
        else:
            resident = sum(int(v.nbytes) for v in values.values()
                           if isinstance(v, jax.Array))
            uploaded = sum(int(v.nbytes) for v in values.values()) - resident
            with tr.span("kernel.quantize_batch", "kernel", fmt=fmt,
                         items=len(values), resident_bytes=resident,
                         uploaded_bytes=uploaded):
                out.update(_fused_quantize_group(values, fmt))
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
