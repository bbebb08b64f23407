"""Wire-plane throughput: GB/s and items/s through the full pipeline
stack, per stage combination, new zero-copy path vs. the pre-refactor
copying path.

Each case pushes an LLM-shaped state dict (many tensors, like a real
transformer checkpoint) through container streaming over loopback —
stage encode, chunk framing, reassembly, stage decode, and a
streaming-fold consume (each decoded item handed downstream and
dropped, the server-side aggregation hot path) — and reports:

* ``items_per_s`` — decoded payload items per second end to end,
* ``gbps`` — payload gigabytes per second end to end,
* ``copied`` / ``alloc`` — MemoryMeter byte-copy volume and cumulative
  buffer allocations per transfer (the zero-copy claim, measured).

The ``legacy`` rows re-enact the pre-refactor hot path faithfully:
per-tensor quantize with eager pad/reshape dispatches and a sync per
item, ``tobytes()`` + ``b"".join`` framing, per-chunk byte slices, and
a parts-list + join receiver. Wire bytes are asserted identical between
the two paths (once, outside the timed region) — this benchmark
measures the cost of copies and dispatch, never a format change. The
``speedup`` rows feed the nightly regression gate
(``benchmarks/compare.py`` against ``BENCH_5.json``).
"""
from __future__ import annotations

import json
import struct
import time

import numpy as np

from repro.core import pipeline as pl
from repro.core import serialization as ser
from repro.core import streaming as sm
from repro.core.messages import Message, MessageKind
from repro.utils import mem
from repro.utils.mem import MemoryMeter

try:
    import zstandard  # noqa: F401
    COMPRESS = "zstd:3"
except ImportError:
    # image without zstd: zlib stored-blocks mode is the closest stand-in
    # for zstd:3's speed class on quantized payloads — on high-entropy
    # nf4 bytes both effectively store (zstd's fast match search finds
    # nothing), whereas deflate's match search at level>=1 runs ~20 MB/s
    # and would make every path compressor-bound, hiding the wire costs
    # this benchmark exists to measure
    COMPRESS = "zlib:0"

CHUNK = 1 << 18

_QSTACK = ["quantize:nf4", COMPRESS, "crc32"]
_QNAME = f"nf4-{COMPRESS.split(':')[0]}-crc32"

#: stage stacks under measurement: (stages, decode_values). The
#: acceptance case is the quantize -> compress -> crc32
#: container-streaming path; its ``wireform`` variant keeps items in
#: wire form on the receiver (``decode_values=False`` — the quantized
#: streaming-aggregation server fold, where the fused
#: dequant-accumulate kernel consumes payloads directly)
STACKS = {
    "plain": ([], True),
    "crc32": (["crc32"], True),
    "nf4": (["quantize:nf4"], True),
    _QNAME: (_QSTACK, True),
    f"{_QNAME}-wireform": (_QSTACK, False),
}


def model_dict(layers: int = 32, d: int = 96):
    """A transformer-shaped dict: many medium tensors (the regime where
    per-item dispatch+copy overhead dominates, as in real LLM
    checkpoints with hundreds of layers)."""
    rng = np.random.default_rng(0)
    sd = {}
    for i in range(layers):
        sd[f"layers.{i}.attn.w"] = rng.standard_normal((d, d)).astype(np.float32)
        sd[f"layers.{i}.mlp.w"] = rng.standard_normal((2 * d, d)).astype(np.float32)
        sd[f"layers.{i}.norm"] = rng.standard_normal((d,)).astype(np.float32)
    return sd


def _message(sd):
    return Message(MessageKind.TASK_RESULT, dict(sd),
                   {"client": "site-0", "num_samples": 1})


class _FoldSink:
    """Streaming-aggregation-shaped consumer: touches each decoded item
    and drops it (the O(item) server fold loop)."""

    def __init__(self):
        self.items = 0

    def __call__(self, name, value):
        self.items += 1


def _wire_tap(driver_cls=sm.LoopbackDriver):
    sent = bytearray()

    class _Tap(driver_cls):
        def send(self, chunk):
            for seg in chunk.segments:
                sent.extend(seg)
            super().send(chunk)

    return _Tap(), sent


# ---------------------------------------------------------------------------
# new path: scatter-gather views end to end
# ---------------------------------------------------------------------------

def run_new(stack, sd, tap: bool = False, decode_values: bool = True):
    """One transfer over the current wire; with ``tap`` the raw wire
    bytes are captured and returned (for the bitwise cross-check)."""
    p = pl.build_pipeline(list(stack), decode_values=decode_values)
    if tap:
        driver, sent = _wire_tap()
    else:
        driver, sent = sm.LoopbackDriver(), None
    decoder = p.decoder()
    sink = _FoldSink()

    def consume(name, value):
        if name != pl.META_ITEM:
            sink(name, value)

    recv = sm.ContainerReceiver(consume=consume, decode_item=decoder.decode_item)
    driver.connect(recv.on_chunk)
    msg, ctx = p.begin_encode(_message(sd))
    sm.ContainerStreamer(driver, CHUNK).send_items(
        p.iter_encode_views(msg, ctx), p.n_items(msg))
    assert sink.items == len(sd)
    return bytes(sent) if tap else None


# ---------------------------------------------------------------------------
# legacy path: the pre-refactor copying pipeline, re-enacted
# ---------------------------------------------------------------------------

def _legacy_quantize(value, fmt):
    """Pre-refactor quantize: eager flatten/astype/pad dispatches
    followed by the 2-D jitted kernel — several dispatches and one sync
    per tensor (the new path fuses these into one async dispatch and
    blocks once per message)."""
    import jax

    from repro.core.quantization import QuantizedTensor
    from repro.kernels import ops

    arr = np.asarray(value)
    if fmt in ("fp4", "nf4"):
        payload, absmax = ops._REF_Q4[fmt](ops._flat_blocks(arr, ops.BLOCK4))
    elif fmt == "blockwise8":
        payload, absmax = ops._REF_Q8(ops._flat_blocks(arr, ops.BLOCK8))
    else:
        raise ValueError(fmt)
    jax.block_until_ready((payload, absmax))  # the per-item sync
    return QuantizedTensor(payload, absmax, fmt, tuple(arr.shape), arr.dtype)


def _legacy_serialize_item(name, value) -> bytes:
    """Pre-refactor serialize: every buffer exported with ``tobytes``
    (copy), then joined (copy)."""
    views = ser.serialize_item_views(name, value)
    parts = []
    for v in views:
        b = bytes(v)
        mem.record_copy(len(b))
        parts.append(b)
    out = b"".join(parts)
    mem.record_copy(len(out))
    return out


def _legacy_encode_item(p, name, value, ctx) -> bytes:
    from repro.core.quantization import QuantizedTensor

    vmetas = []
    for s in p._vstages:
        ctx.vmeta = {}
        if isinstance(s, pl.QuantizeStage) and s.fmt in ("nf4", "fp4", "blockwise8") \
                and not isinstance(value, QuantizedTensor) \
                and np.issubdtype(np.asarray(value).dtype, np.floating):
            value = _legacy_quantize(value, s.fmt)
        else:
            value = s.encode_item(name, value, ctx)
        vmetas.append(ctx.vmeta)
    inner = _legacy_serialize_item(name, value)
    body = inner
    brecs = []
    for s in p._bstages:
        bmeta = {}
        body = s.encode_item_bytes(name, body, bmeta, ctx)
        brecs.append([s.name, bmeta])
    if not p._vstages and not p._bstages:
        return inner
    header = {"kind": "wire", "name": name, "n": len(body),
              "v": [s.name for s in p._vstages], "b": brecs}
    if vmetas and any(vmetas):
        header["vm"] = vmetas
    hb = json.dumps(header, sort_keys=True).encode()
    out = struct.pack("<I", len(hb)) + hb + body
    mem.record_copy(len(out))
    return out


class _LegacyReceiver:
    """Pre-refactor ContainerReceiver: parts list, join per item."""

    def __init__(self, decode_item, consume):
        self._parts = []
        self._size = 0
        self._decode = decode_item
        self._consume = consume

    def on_chunk(self, chunk):
        b = chunk.payload_bytes()
        self._parts.append(b)
        mem.record_alloc(len(b))
        self._size += len(b)
        if chunk.item_end:
            buf = b"".join(self._parts)
            mem.record_copy(len(buf))
            mem.record_alloc(len(buf))
            name, value, _ = self._decode(bytes(buf))
            mem.record_free(len(buf) + self._size)
            self._parts.clear()
            self._size = 0
            self._consume(name, value)


def run_legacy(stack, sd, tap: bool = False, decode_values: bool = True):
    p = pl.build_pipeline(list(stack), decode_values=decode_values)
    if tap:
        driver, sent = _wire_tap()
    else:
        driver, sent = sm.LoopbackDriver(), None
    decoder = p.decoder()
    sink = _FoldSink()

    def consume(name, value):
        if name != pl.META_ITEM:
            sink(name, value)

    recv = _LegacyReceiver(decoder.decode_item, consume)
    driver.connect(recv.on_chunk)
    msg = _message(sd)
    # no begin_encode batching: the legacy loop encoded item by item
    ctx = pl.WireContext(msg.headers, p.decode_values)
    for s in p.stages:
        if not isinstance(s, pl.QuantizeStage):
            msg = s.begin_encode(msg, ctx)
        else:
            ctx.headers["quantized_fmt"] = s._fmt_label()
    streamer = sm.ContainerStreamer(driver, CHUNK)

    def iter_items():
        yield pl.META_ITEM, ser.join_views(p._encode_meta(msg, ctx))
        for name, value in msg.payload.items():
            blob = _legacy_encode_item(p, name, value, ctx)
            with mem.record_hold(len(blob)):
                # pre-refactor chunking sliced bytes (a copy per chunk)
                parts = [bytes(memoryview(blob)[o:o + CHUNK])
                         for o in range(0, len(blob), CHUNK)]
                for part in parts:
                    mem.record_copy(len(part))
                yield name, parts

    streamer.send_items(iter_items(), p.n_items(msg))
    assert sink.items == len(sd)
    return bytes(sent) if tap else None


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

def _time_path(fn, stack, sd, repeats, decode_values):
    meter = MemoryMeter()
    fn(stack, sd, decode_values=decode_values)  # warm jit caches untimed
    best = float("inf")
    with meter.activate():
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn(stack, sd, decode_values=decode_values)
            # best-of-N: robust to scheduler noise on shared CI runners,
            # and equally generous to both paths
            best = min(best, time.perf_counter() - t0)
    return best, meter


def _bench_case(sname, stack, sd, repeats, decode_values=True):
    payload = sum(v.nbytes for v in sd.values())
    n_items = len(sd)
    # bitwise cross-check, outside the timed region
    assert run_new(stack, sd, tap=True) == run_legacy(stack, sd, tap=True), \
        f"wire bytes diverged on {sname}"
    per_new, m_new = _time_path(run_new, stack, sd, repeats, decode_values)
    per_old, m_old = _time_path(run_legacy, stack, sd, repeats, decode_values)
    rows = []
    for path, per, meter in (("new", per_new, m_new), ("legacy", per_old, m_old)):
        rows.append(
            f"wire/{sname}/{path},{per * 1e6:.0f},"
            f"items_per_s={n_items / per:.0f};"
            f"gbps={payload / per / 1e9:.3f};"
            f"copied={meter.copied // repeats};"
            f"alloc={meter.total_allocated // repeats}"
        )
    rows.append(
        f"wire/{sname}/speedup,0,"
        f"new_over_legacy={per_old / per_new:.2f};"
        f"copy_reduction={m_old.copied / max(m_new.copied, 1):.2f}"
    )
    return rows


def run(repeats: int = 5) -> list[str]:
    sd = model_dict()
    rows = []
    for sname, (stack, decode_values) in STACKS.items():
        rows.extend(_bench_case(sname, stack, sd, repeats,
                                decode_values=decode_values))
    # framing throughput on embedding-sized tensors: the regime where
    # the joins/copies the refactor removed were memcpy-bound
    big = {f"embed.{i}": np.random.default_rng(i).standard_normal(
        (2048, 2048)).astype(np.float32) for i in range(4)}  # 4 x 16 MiB
    rows.extend(_bench_case("plain-big", [], big, max(repeats // 2, 2)))
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
