"""Zero-copy wire plane: golden-bytes regression suite + copy accounting.

The scatter-gather refactor (view framing, batched quantize dispatch,
preallocated receive buffers) is a pure hot-path rework — wire bytes
must be **bitwise identical** to the pre-refactor wire. The hashes
below were captured from the joined-bytes implementation immediately
before the refactor and pin the full container stream (every envelope,
in order, length-prefixed) for representative stage stacks, including
the stateful ``delta`` stage across two rounds (full-snapshot and
residual paths both covered).

The copy-count tests assert the other half of the claim: a transfer
now moves each payload byte at most ~once (MemoryMeter ``copied``) and
allocates ~2x the item size (sender hold + receiver buffer) where the
old path copied every byte 4-6x.
"""
import hashlib
import socket
import struct
import threading

import numpy as np
import pytest

from repro.core import pipeline as pl
from repro.core import serialization as ser
from repro.core import streaming as sm
from repro.core.messages import Message, MessageKind
from repro.utils.mem import MemoryMeter

# sha256 over the pre-refactor container stream: for each item (meta
# first), u64-LE length then the envelope bytes; two rounds per stack
GOLDEN = {
    "nf4-delta-zlib-crc32": "31020ea62b809910e1d728215472111b1f5e9c7aad5c944ecf5e8bb039961809",
    "nf4-zlib-crc32": "9772001f25dab132f65cf410d40c6b0b6072a3f032f360ae9bb6fc60acc7baca",
    "blockwise8": "8f89d45f32e4db30467d7a05ffb189e862b9a8f062fa010f0596cdaa2c2b1379",
    "plain": "7c00654d6d6d40ca6aa6d5733aec3923028d62eba7d8428fc58bb56da5342869",
}

STACKS = {
    "nf4-delta-zlib-crc32": ["quantize:nf4", "delta", "zlib", "crc32"],
    "nf4-zlib-crc32": ["quantize:nf4", "zlib", "crc32"],
    "blockwise8": ["quantize:blockwise8"],
    "plain": [],
}


def _golden_sd():
    rng = np.random.default_rng(42)
    return {
        "embed.w": rng.standard_normal((96, 64)).astype(np.float32),
        "layers.0.attn.wq": rng.standard_normal((64, 64)).astype(np.float32),
        "layers.0.norm": rng.standard_normal((64,)).astype(np.float32),
        "step": np.asarray(123, np.int32),
    }


def _stream_hash(pipeline, rounds=2, via_views=False):
    h = hashlib.sha256()
    for rnd in range(rounds):
        m = Message(MessageKind.TASK_RESULT, _golden_sd(),
                    {"client": "site-0", "round": rnd, "num_samples": 17})
        msg, ctx = pipeline.begin_encode(m)
        if via_views:
            items = ((n, ser.join_views(v))
                     for n, v in pipeline.iter_encode_views(msg, ctx))
        else:
            items = pipeline.iter_encode(msg, ctx)
        for _name, blob in items:
            h.update(len(blob).to_bytes(8, "little"))
            h.update(blob)
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_wire_bytes_bitwise_identical_to_pre_refactor(name):
    assert _stream_hash(pl.build_pipeline(STACKS[name])) == GOLDEN[name]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_view_and_joined_producers_agree(name):
    """iter_encode_views joined == iter_encode bytes — one wire format,
    two access patterns."""
    assert _stream_hash(pl.build_pipeline(STACKS[name]), via_views=True) \
        == GOLDEN[name]


@pytest.mark.parametrize("chunk_size", [64, 1024, 1 << 20])
def test_chunk_framing_unchanged_across_chunk_sizes(chunk_size):
    """Scatter-gather chunking slices views instead of bytes, but chunk
    payload boundaries (and thus frame bytes) are unchanged."""
    p = pl.build_pipeline(STACKS["nf4-zlib-crc32"])
    m = Message(MessageKind.TASK_RESULT, _golden_sd(), {"num_samples": 3})
    msg, ctx = p.begin_encode(m)
    frames = []
    for _n, views in p.iter_encode_views(msg, ctx):
        joined = ser.join_views(views)
        got = []
        for part, last in sm._chunk_iter_views(views, chunk_size):
            seg = sm.Chunk(b"x" * 16, 0, part, 0)
            got.append(seg.payload_bytes())
            assert len(got[-1]) <= chunk_size
        assert b"".join(got) == joined
        assert all(len(g) == chunk_size for g in got[:-1])
        frames.append(got)
    assert frames


def test_zstd_envelope_bitwise_stable_roundtrip():
    """When zstd is importable its envelopes decode back bit-exact and
    the encode is deterministic (the golden property, checked
    structurally because the hash cannot be pinned on images without
    zstd)."""
    pytest.importorskip("zstandard")
    p = pl.build_pipeline(["quantize:nf4", "zstd:3", "crc32"])
    m = Message(MessageKind.TASK_RESULT, _golden_sd(), {"num_samples": 1})
    msg, ctx = p.begin_encode(m)
    blobs = [blob for _n, blob in p.iter_encode(msg, ctx)]
    msg2, ctx2 = p.begin_encode(
        Message(MessageKind.TASK_RESULT, _golden_sd(), {"num_samples": 1}))
    assert blobs == [blob for _n, blob in p.iter_encode(msg2, ctx2)]


# ---------------------------------------------------------------------------
# copy / allocation accounting
# ---------------------------------------------------------------------------

def _transfer(sd, chunk_size=1 << 16, stack=()):
    """One container-streamed transfer over loopback; returns the meter."""
    p = pl.build_pipeline(list(stack))
    meter = MemoryMeter()
    with meter.activate():
        driver = sm.LoopbackDriver()
        decoder = p.decoder()
        seen = []
        recv = sm.ContainerReceiver(consume=lambda n, v: seen.append(n),
                                    decode_item=decoder.decode_item)
        driver.connect(recv.on_chunk)
        msg, ctx = p.begin_encode(
            Message(MessageKind.TASK_RESULT, dict(sd), {"num_samples": 1}))
        sm.ContainerStreamer(driver, chunk_size).send_items(
            p.iter_encode_views(msg, ctx), p.n_items(msg))
    assert len(seen) == len(sd) + 1
    return meter


def test_one_item_transfer_copies_each_byte_at_most_once():
    """A 1-MiB tensor crossing the wire in 64-KiB chunks is copied once
    (chunk segments into the preallocated receive buffer) — the old
    path's tobytes + envelope join + chunk slices + receiver join +
    decode cast copied every byte 4-6x."""
    item = np.random.default_rng(0).standard_normal((512, 512)).astype(np.float32)
    meter = _transfer({"w": item})
    assert meter.copied <= 1.2 * item.nbytes
    # allocations: sender in-flight hold + receiver's single buffer
    # (+ small header/meta noise), nowhere near the old 4x
    assert meter.total_allocated <= 2.5 * item.nbytes
    assert meter.peak <= 2.2 * item.nbytes
    assert meter.live == 0


def test_single_chunk_items_receive_zero_copy():
    """Items smaller than the chunk size decode straight off the chunk
    segments: the segment-aware inner decoder reads the envelope header
    from segment 0 and ``frombuffer``s the payload from segment 1, so a
    plain single-chunk receive copies **zero** payload bytes — not even
    the old single header+payload join."""
    sd = {f"l{i}": np.random.default_rng(i).standard_normal((64, 64))
          .astype(np.float32) for i in range(8)}
    meter = _transfer(sd, chunk_size=1 << 20)
    assert meter.copied == 0


def test_single_chunk_byte_staged_items_copy_at_most_once():
    """Byte stages (zlib/crc) need contiguous input, so a staged stack
    still joins once — but never the old join-then-slice double
    handling."""
    sd = {f"l{i}": np.random.default_rng(i).standard_normal((64, 64))
          .astype(np.float32) for i in range(8)}
    meter = _transfer(sd, chunk_size=1 << 20,
                      stack=["quantize:blockwise8", "crc32"])
    payload = sum(v.nbytes for v in sd.values())
    assert meter.copied <= 1.1 * payload


def test_multi_chunk_receiver_preallocates_single_buffer():
    """The reassembly buffer is allocated once, from the item header's
    declared length, and filled in place — live receive memory during a
    big item is ~item + chunk, not parts-list + join (2x)."""
    item = np.zeros((256, 1024), np.float32)  # 1 MiB
    meter = _transfer({"w": item}, chunk_size=4096)
    assert meter.peak <= 2.2 * item.nbytes


def test_legacy_benchmark_path_matches_and_copies_more():
    """The re-enacted pre-refactor path (benchmarks/wire_throughput)
    produces identical wire bytes while copying >=2x more — the
    acceptance comparison, pinned as a test."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import wire_throughput as wt

    sd = {f"l{i}": np.random.default_rng(i).standard_normal((128, 128))
          .astype(np.float32) for i in range(6)}
    stack = ["quantize:blockwise8", "crc32"]
    assert wt.run_new(stack, sd, tap=True) == wt.run_legacy(stack, sd, tap=True)
    m_new, m_old = MemoryMeter(), MemoryMeter()
    with m_new.activate():
        wt.run_new(stack, sd)
    with m_old.activate():
        wt.run_legacy(stack, sd)
    assert m_old.copied >= 2 * m_new.copied


# ---------------------------------------------------------------------------
# scatter-gather TCP driver
# ---------------------------------------------------------------------------

def test_tcp_driver_scatter_gather_roundtrip():
    """Multi-segment chunks above the coalescing threshold go out via
    sendmsg (scatter-gather syscall); small chunks coalesce into one
    write. Either way the receiver sees the exact stream."""
    sd = {"big": np.random.default_rng(0).standard_normal((256, 256))
          .astype(np.float32),  # 256 KiB > COALESCE_BYTES
          "small": np.arange(16, dtype=np.float32)}
    driver = sm.TCPDriver()
    recv = sm.ContainerReceiver()
    driver.connect(recv.on_chunk)
    sm.ContainerStreamer(driver, 1 << 20).send_container(sd)
    driver.close()
    assert recv.done
    np.testing.assert_array_equal(recv.result["big"], sd["big"])
    np.testing.assert_array_equal(recv.result["small"], sd["small"])


def test_tcp_sendmsg_handles_partial_sends():
    """A tiny socket send buffer forces partial sendmsg returns; the
    driver must resume mid-segment without corrupting the stream."""
    received = bytearray()
    done = threading.Event()
    srv = socket.create_server(("127.0.0.1", 0))

    def serve():
        conn, _ = srv.accept()
        with conn:
            while True:
                b = conn.recv(65536)
                if not b:
                    break
                received.extend(b)
        done.set()

    t = threading.Thread(target=serve, daemon=True)
    t.start()

    drv = sm.TCPDriver.__new__(sm.TCPDriver)
    drv._sock = socket.create_connection(srv.getsockname())
    drv._sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    payload = tuple(memoryview(bytes([i] * 40000)) for i in range(4))
    chunk = sm.Chunk(b"s" * 16, 0, payload, sm.FLAG_EOF)
    drv.send(chunk)
    drv._sock.close()
    done.wait(5)
    srv.close()
    assert bytes(received) == chunk.encode()


# ---------------------------------------------------------------------------
# batched quantize dispatch + fused folds: behavioural pins
# ---------------------------------------------------------------------------

def test_quantize_batch_bitwise_equals_per_item_quantize():
    from repro.core.quantization import quantize, quantize_batch

    rng = np.random.default_rng(7)
    sd = {f"t{i}": rng.standard_normal((65 + i, 33)).astype(np.float32)
          for i in range(5)}
    for fmt in ("nf4", "fp4", "blockwise8", "fp16"):
        batched = quantize_batch(sd, {k: fmt for k in sd})
        for k, v in sd.items():
            solo = quantize(np.asarray(v), fmt)
            np.testing.assert_array_equal(np.asarray(batched[k].payload),
                                          np.asarray(solo.payload))
            if solo.absmax is not None:
                np.testing.assert_array_equal(np.asarray(batched[k].absmax),
                                              np.asarray(solo.absmax))
            assert batched[k].orig_shape == solo.orig_shape


def test_quantize_batch_mixed_formats_and_passthrough():
    from repro.core.quantization import quantize_batch

    sd = {"a": np.ones((64,), np.float32), "b": np.ones((128,), np.float32),
          "c": np.ones((8,), np.float32)}
    out = quantize_batch(sd, {"a": "nf4", "b": "blockwise8"})
    assert set(out) == {"a", "b"}
    assert out["a"].fmt == "nf4" and out["b"].fmt == "blockwise8"


def test_prequant_skipped_when_quantize_is_not_first_value_stage():
    """A value stage ahead of quantize rewrites items, so the batched
    dispatch must not run on stale payloads — the wire still carries
    the correct (noised, then quantized) values."""
    p = pl.WirePipeline([pl.build_stage({"stage": "dp-noise", "sigma": 0.5,
                                         "seed": 1}),
                         pl.build_stage("quantize:blockwise8")])
    x = np.zeros((4096,), np.float32)
    msg, ctx = p.begin_encode(
        Message(MessageKind.TASK_RESULT, {"w": x.copy()}, {}))
    blob = p.encode_wire_item("w", msg.payload["w"], ctx)
    _name, value, _ = p.decoder().decode_item(blob)
    # noise survived into the quantized stream (std ~0.5, not 0)
    assert 0.2 < float(np.std(np.asarray(value))) < 0.8


def test_dequant_accumulate_into_matches_unfused():
    from repro.kernels import ops, ref

    rng = np.random.default_rng(3)
    xs = [rng.standard_normal((3, 4096)).astype(np.float32) for _ in range(4)]
    ws = [0.5, 1.5, 2.0, 3.0]
    acc = None
    for x, w in zip(xs, ws):
        q, am = ops.quantize_blockwise8(x)
        acc = ops.dequant_accumulate8_into(acc, q, am, w)
    want = sum(
        w * np.asarray(ref.dequantize_blockwise8(*ops.quantize_blockwise8(x)))
        for x, w in zip(xs, ws)
    )
    # the pallas path may row-pad the donated accumulator (documented
    # contract: callers slice to the original element count, as the
    # streaming aggregator does)
    got = np.asarray(acc)[: want.shape[0]]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dequant_accumulate_into_pallas_interpret_matches_ref():
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.fused_dequant_agg import (
        ROWS,
        dequant_accumulate8_into_pallas,
    )

    rng = np.random.default_rng(5)
    x = rng.standard_normal((ROWS * 2, 4096)).astype(np.float32)
    q, am = ops.quantize_blockwise8(x)
    acc0 = rng.standard_normal((ROWS * 2, 4096)).astype(np.float32)
    # both entry points donate the accumulator: hand each its own copy
    ref_out = np.asarray(ops._REF_FOLD8(jnp.array(acc0), jnp.asarray(q),
                                        jnp.asarray(am), jnp.float32(2.5)))
    got = dequant_accumulate8_into_pallas(
        jnp.array(acc0), jnp.asarray(q), jnp.asarray(am),
        jnp.float32(2.5), interpret=True)
    np.testing.assert_allclose(np.asarray(got), ref_out, rtol=1e-6, atol=1e-6)


def test_quantized_fedavg_state_is_one_accumulator_per_tensor():
    """The streaming fold never buffers per-client payloads: after K
    contributions the aggregator holds exactly one accumulator per
    tensor name."""
    from repro.core.quantization import quantize
    from repro.fl.aggregator import QuantizedFedAvgAggregator

    rng = np.random.default_rng(11)
    agg = QuantizedFedAvgAggregator()
    for k in range(6):
        w = agg.begin({"num_samples": k + 1})
        for name in ("a", "b"):
            qt = quantize(rng.standard_normal((5000,)).astype(np.float32),
                          "blockwise8")
            agg.accept_item(name, qt, w)
        assert len(agg._acc) == 2  # never K x payloads
    out = agg.finish()
    assert set(out) == {"a", "b"} and out["a"].shape == (5000,)


def test_delta_stage_keeps_one_canonical_snapshot_in_process():
    """When one instance serves both wire ends, encoder and decoder
    share the snapshot object — one array per (client, tensor), not
    two."""
    p = pl.WirePipeline([pl.build_stage("delta")])
    x = np.linspace(-1, 1, 256).astype(np.float32)
    for rnd in range(3):
        msg, ctx = p.begin_encode(
            Message(MessageKind.TASK_RESULT, {"w": x + rnd}, {"client": "c"}))
        dec = p.decoder()  # meta item first, so the client header decodes
        out = {}
        for _n, blob in p.iter_encode(msg, ctx):
            name, value, _ = dec.decode_item(blob)
            dec.on_item(name, value)
            out[name] = value
        np.testing.assert_allclose(np.asarray(out["w"]), x + rnd, atol=1e-6)
    stage = p.stages[0]
    key = ("c", "w")
    assert stage._prev_dec[key] is stage._prev_enc[key]


def test_stage_overriding_only_views_hook_runs_on_the_wire(monkeypatch):
    """A byte stage may override only encode_item_views (the streaming
    hook); it must still be scheduled and its meta recorded in the
    envelope."""
    # a registration is process-global and enters every pipeline
    # fingerprint: keep it out of the tests that share this worker
    monkeypatch.setattr(pl, "_STAGES", dict(pl._STAGES))
    name = "test-views-only-tag"
    if name not in pl.registered_stages():
        @pl.register_stage(name)
        class _ViewsTag(pl.Stage):
            def encode_item_views(self, n, views, meta, ctx):
                meta["len"] = ser.views_nbytes(views)
                return views

    p = pl.build_pipeline([name])
    m = Message(MessageKind.TASK_RESULT, {"w": np.arange(8, dtype=np.float32)}, {})
    msg, ctx = p.begin_encode(m)
    blob = p.encode_wire_item("w", msg.payload["w"], ctx)
    (hlen,) = struct.unpack_from("<I", blob, 0)
    import json
    header = json.loads(bytes(blob[4:4 + hlen]))
    assert header["b"] and header["b"][0][0] == name
    assert header["b"][0][1]["len"] == header["n"]
    _n, value, _ = p.decoder().decode_item(blob)
    np.testing.assert_array_equal(np.asarray(value), np.arange(8, dtype=np.float32))


def test_declared_item_nbytes_covers_every_wire_kind():
    from repro.core.quantization import quantize
    from repro.core.sparse import topk_sparsify
    from repro.peft.lowrank import LowRankDelta

    x = np.random.default_rng(0).standard_normal((37, 21)).astype(np.float32)
    lrd = LowRankDelta(x[:, :4].copy(), x[:4, :].copy(), 4.0, 4,
                       (37, 21), np.float32)
    for value in (x, np.asarray(5, np.int64), quantize(x, "nf4"),
                  quantize(x, "blockwise8"), topk_sparsify(x, 0.1), lrd):
        blob = ser.serialize_item("w", value)
        assert ser.declared_item_nbytes(blob) == len(blob)
        # a partial prefix (header not yet complete) reports unknown
        assert ser.declared_item_nbytes(blob[:3]) is None
