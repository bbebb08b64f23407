"""Wire pipeline: golden-bytes framing, per-stage round trips, ordered
stacks, legacy FilterChain-shim equivalence, the O(largest item) peak
transmission-memory envelope with quantization enabled (the composition
the pipeline redesign exists for), and chunk-level fault injection
feeding retransmitted bytes back into simulated transfer time.
"""
import json
import struct

import numpy as np
import pytest

from repro.core import pipeline as pl
from repro.core import serialization as ser
from repro.core.filters import no_filters, two_way_quantization
from repro.core.messages import Message, MessageKind
from repro.core.quantization import QuantizedTensor, quantize
from repro.fl import FedAvgAggregator, FLSimulator, SimulationConfig, TrainExecutor
from repro.runtime import LinkProfile, NetworkModel, RuntimeConfig


def _msg(payload, **headers):
    return Message(MessageKind.TASK_RESULT, dict(payload), dict(headers))


def _roundtrip(pipeline, message):
    """Encode a message through the pipeline and decode it back,
    item-for-item, the way the simulator wire does."""
    msg, ctx = pipeline.begin_encode(message)
    dec = pipeline.decoder()
    for _name, blob in pipeline.iter_encode(msg, ctx):
        name, value, consumed = dec.decode_item(blob)
        assert consumed == len(blob)
        dec.on_item(name, value)
    return dec.finish(msg.kind, pipeline.unsent_headers(msg))


def _sd(seed=0, items=4, shape=(64, 32)):
    rng = np.random.default_rng(seed)
    return {f"layer.{i}.w": rng.standard_normal(shape).astype(np.float32)
            for i in range(items)}


# ---------------------------------------------------------------------------
# golden bytes / framing
# ---------------------------------------------------------------------------

def test_empty_pipeline_is_byte_compatible_with_plain_serialization():
    """A stage-less pipeline frames items exactly like the inner codec —
    the pre-pipeline wire format, byte for byte."""
    p = pl.build_pipeline([])
    m = _msg(_sd(items=2))
    msg, ctx = p.begin_encode(m)
    envs = {name: blob for name, blob in p.iter_encode(msg, ctx) if name != pl.META_ITEM}
    for name, value in m.payload.items():
        assert envs[name] == ser.serialize_item(name, value)


def test_plain_item_golden_bytes():
    """The inner item framing is locked: u32 header length, sorted-key
    JSON header, raw C-order array bytes."""
    arr = np.arange(4, dtype=np.float32)
    header = b'{"dtype": "float32", "kind": "array", "name": "w", "shape": [4]}'
    golden = struct.pack("<I", len(header)) + header + arr.tobytes()
    assert ser.serialize_item("w", arr) == golden


def test_wire_envelope_carries_stage_metadata():
    """Envelope header records the stage stack (names + per-stage meta),
    is valid sorted-key JSON, and encoding is deterministic."""
    p = pl.build_pipeline(["quantize:nf4", "zlib", "crc32"])
    m = _msg({"w": np.linspace(-1, 1, 256).astype(np.float32)})
    msg, ctx = p.begin_encode(m)
    blob = p.encode_wire_item("w", msg.payload["w"], ctx)
    blob2 = p.encode_wire_item("w", msg.payload["w"], ctx)
    assert blob == blob2  # deterministic bytes
    (hlen,) = struct.unpack_from("<I", blob, 0)
    header = json.loads(blob[4:4 + hlen])
    assert header["kind"] == "wire" and header["name"] == "w"
    assert header["v"] == ["quantize"]
    assert [b[0] for b in header["b"]] == ["zlib", "crc32"]
    assert "crc" in header["b"][1][1] and "n" in header["b"][0][1]
    assert header["n"] == len(blob) - 4 - hlen


def test_message_headers_cross_the_wire():
    out = _roundtrip(pl.build_pipeline(["crc32"]),
                     _msg({"w": np.ones(8, np.float32)}, round=3, client="site-1",
                          metrics={"loss": 0.125}))
    assert out.headers["round"] == 3
    assert out.headers["client"] == "site-1"
    assert out.headers["metrics"] == {"loss": 0.125}
    assert out.kind is MessageKind.TASK_RESULT


# ---------------------------------------------------------------------------
# per-stage round trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt,tol", [("fp16", 1e-3), ("blockwise8", 0.03), ("nf4", 0.6)])
def test_quantize_stage_roundtrip(fmt, tol):
    m = _msg({"w": np.random.default_rng(0).standard_normal((65, 33)).astype(np.float32),
              "step": np.asarray(7, np.int32)})
    out = _roundtrip(pl.build_pipeline([f"quantize:{fmt}"]), m)
    np.testing.assert_allclose(np.asarray(out.payload["w"]), m.payload["w"], atol=tol)
    assert int(out.payload["step"]) == 7  # non-float passes through
    assert "quantized_fmt" not in out.headers  # popped after decode


def test_quantize_stage_keeps_wire_form_when_decode_values_off():
    p = pl.build_pipeline(["quantize:blockwise8"], decode_values=False)
    out = _roundtrip(p, _msg({"w": np.ones((64,), np.float32)}))
    assert isinstance(out.payload["w"], QuantizedTensor)
    assert out.headers["quantized_fmt"] == "blockwise8"  # header kept too


def test_zlib_stage_roundtrip_and_actually_compresses():
    m = _msg({"w": np.zeros((1 << 14,), np.float32)})
    p = pl.build_pipeline(["zlib"])
    msg, ctx = p.begin_encode(m)
    blob = p.encode_wire_item("w", msg.payload["w"], ctx)
    assert len(blob) < m.payload["w"].nbytes / 50  # zeros compress hard
    out = _roundtrip(p, m)
    np.testing.assert_array_equal(np.asarray(out.payload["w"]), m.payload["w"])


def test_zlib_stage_rejects_length_mismatch():
    """Decompression is bounded by the envelope-declared original length:
    a stream that inflates past (or under) its declaration is rejected
    instead of expanding unbounded."""
    p = pl.build_pipeline(["zlib"])
    m = _msg({"w": np.zeros((4096,), np.float32)})
    msg, ctx = p.begin_encode(m)
    blob = p.encode_wire_item("w", msg.payload["w"], ctx)
    (hlen,) = struct.unpack_from("<I", blob, 0)
    header = json.loads(blob[4:4 + hlen])
    header["b"][0][1]["n"] //= 2  # lie about the original length
    hb = json.dumps(header, sort_keys=True).encode()
    # note: header["n"] (compressed body length) is unchanged
    tampered = struct.pack("<I", len(hb)) + hb + blob[4 + hlen:]
    with pytest.raises(pl.WireIntegrityError, match="declared"):
        p.decoder().decode_item(tampered)


def test_crc32_stage_rejects_corruption():
    p = pl.build_pipeline(["crc32"])
    m = _msg({"w": np.arange(64, dtype=np.float32)})
    msg, ctx = p.begin_encode(m)
    blob = bytearray(p.encode_wire_item("w", msg.payload["w"], ctx))
    blob[-1] ^= 0xFF  # flip one payload byte
    with pytest.raises(pl.WireIntegrityError, match="crc32 mismatch"):
        p.decoder().decode_item(bytes(blob))


def test_dp_noise_stage_adds_noise_once():
    m = _msg({"w": np.zeros((4096,), np.float32)})
    out = _roundtrip(pl.build_pipeline([{"stage": "dp-noise", "sigma": 0.1, "seed": 3}]), m)
    std = float(np.std(np.asarray(out.payload["w"])))
    assert 0.08 < std < 0.12  # noised on encode, identity on decode


def test_ef_quantize_stage_residual_shrinks_error():
    """Error feedback: repeated transmissions of the same tensor drive the
    *cumulative* quantization error toward zero (EF-SGD mechanism)."""
    x = np.random.default_rng(5).standard_normal((256,)).astype(np.float32)
    stage = pl.build_stage("ef-quantize:nf4")
    p = pl.WirePipeline([stage])
    recovered = []
    for _ in range(30):
        out = _roundtrip(p, _msg({"w": x.copy()}))
        recovered.append(np.asarray(out.payload["w"], np.float32))
    plain = _roundtrip(pl.build_pipeline(["quantize:nf4"]), _msg({"w": x.copy()}))
    err_plain = np.abs(np.asarray(plain.payload["w"]) - x).mean()
    err_ef = np.abs(np.mean(recovered, axis=0) - x).mean()
    assert err_ef < err_plain / 3  # residual carry-over averages out


def test_ef_quantize_residuals_are_per_client():
    """One ef-quantize stage serves a whole hop direction; the ``client``
    header keeps each site's error stream independent (client B must not
    inherit client A's residual)."""
    x = np.random.default_rng(7).standard_normal((256,)).astype(np.float32)
    shared = pl.WirePipeline([pl.build_stage("ef-quantize:nf4")])

    def one_client_sequence(pipeline, client):
        return [np.asarray(
            _roundtrip(pipeline, _msg({"w": x.copy()}, client=client)).payload["w"],
            np.float32,
        ) for _ in range(4)]

    seq_a = one_client_sequence(shared, "site-a")
    seq_b = one_client_sequence(shared, "site-b")
    # a dedicated stage for one client reproduces the shared stage's
    # stream exactly — interleaving another client changed nothing
    solo = one_client_sequence(pl.WirePipeline([pl.build_stage("ef-quantize:nf4")]), "site-b")
    for got, want in zip(seq_b, solo):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(seq_a, solo):
        np.testing.assert_array_equal(got, want)


def test_receiver_without_sender_pipeline_decodes_from_envelope():
    """The self-describing envelope claim: a receiver holding only an
    empty pipeline resolves stage names through the registry — including
    stages whose constructors need encode-side args (quantize)."""
    sender = pl.build_pipeline(["quantize:blockwise8", "zlib", "crc32"])
    m = _msg({"w": np.random.default_rng(2).standard_normal((128,)).astype(np.float32)},
             round=1)
    msg, ctx = sender.begin_encode(m)
    receiver = pl.build_pipeline([]).decoder()
    for _name, blob in sender.iter_encode(msg, ctx):
        name, value, _ = receiver.decode_item(blob)
        receiver.on_item(name, value)
    out = receiver.finish(m.kind)
    np.testing.assert_allclose(np.asarray(out.payload["w"]), m.payload["w"], atol=0.03)


def test_legacy_quantize_filters_do_not_serialize_transfers():
    """Stateless legacy filters (the two-way quantization config) must
    not mark the shim pipeline stateful — that would collapse async
    wire concurrency to one transfer at a time."""
    pls = pl.legacy_wire_pipelines(two_way_quantization("nf4"),
                                   two_way_quantization("nf4"))
    assert not pls["task_data"].stateful
    assert not pls["task_result"].stateful
    from repro.core.filters import DPGaussianNoiseFilter, FilterChain, FilterPoint
    noisy = two_way_quantization("nf4")
    noisy[FilterPoint.TASK_RESULT_OUT] = FilterChain([DPGaussianNoiseFilter(0.1)])
    assert pl.legacy_wire_pipelines(noisy, noisy)["task_result"].stateful


def test_adaptive_stage_tracks_per_client_link():
    slow = LinkProfile("slow", bandwidth_mbps=1.0, latency_ms=10.0)
    fast = LinkProfile("fast", bandwidth_mbps=10000.0, latency_ms=1.0)
    net = NetworkModel(profiles={"site-slow": slow, "site-fast": fast})
    stage = pl.build_stage({"stage": "adaptive", "budget_s": 0.5})
    stage.bind_network(net)
    p = pl.WirePipeline([stage])
    payload = {"w": np.ones((1 << 16,), np.float32)}  # 256 KiB
    out_slow = _roundtrip(p, _msg(dict(payload), client="site-slow"))
    out_fast = _roundtrip(p, _msg(dict(payload), client="site-fast"))
    assert stage.last_fmt_by_client["site-slow"] in ("nf4", "blockwise8")
    assert stage.last_fmt_by_client["site-fast"] == "fp32"
    np.testing.assert_array_equal(np.asarray(out_fast.payload["w"]), payload["w"])
    assert np.abs(np.asarray(out_slow.payload["w"]) - payload["w"]).max() < 0.5


def test_secure_mask_stage_masks_telescope():
    from repro.core.secure_agg import SCALE, SecureAggregator

    clients = [0, 1, 2]
    xs = [np.random.default_rng(i).standard_normal((129,)).astype(np.float32)
          for i in clients]
    agg = SecureAggregator(num_clients=3)
    for i in clients:
        p = pl.WirePipeline([pl.SecureMaskStage(i, clients, base_seed=9)])
        out = _roundtrip(p, _msg({"w": xs[i]}, num_samples=1))
        assert out.payload["w"].dtype == np.uint32  # masked on the wire
        agg.accept(out)
    np.testing.assert_allclose(agg.finish()["w"], np.mean(xs, axis=0), atol=3.0 / SCALE)


# ---------------------------------------------------------------------------
# ordered stacks + registry
# ---------------------------------------------------------------------------

def test_stacked_quantize_zlib_crc_roundtrip_through_simulator():
    sd = _sd(items=6)

    def train_fn(params, rnd):
        return {k: np.asarray(v) for k, v in params.items()}, 1, {}

    stack = ["quantize:blockwise8", "zlib", "crc32"]
    sim = FLSimulator(
        [TrainExecutor("s0", train_fn)],
        FedAvgAggregator(),
        SimulationConfig(num_rounds=2, chunk_size=1024),
        pipelines={"task_data": stack, "task_result": stack},
    )
    final = sim.run(dict(sd))
    for k in sd:
        np.testing.assert_allclose(np.asarray(final[k]), sd[k], atol=0.03)
    assert sim.stats.bytes_sent > 0


def test_unknown_stage_name_raises():
    with pytest.raises(ValueError, match="unknown stage"):
        pl.build_pipeline(["carrier-pigeon"])


def test_third_party_stage_registers_and_runs(monkeypatch):
    # a registration is process-global and enters every pipeline
    # fingerprint: keep it out of the tests that share this worker
    monkeypatch.setattr(pl, "_STAGES", dict(pl._STAGES))
    name = "test-negate"
    if name not in pl.registered_stages():
        @pl.register_stage(name)
        class _NegateStage(pl.Stage):
            def encode_item(self, n, v, ctx):
                return -np.asarray(v)

            def decode_item(self, n, v, ctx):
                return -np.asarray(v)

    out = _roundtrip(pl.build_pipeline([name]),
                     _msg({"w": np.arange(8, dtype=np.float32)}))
    np.testing.assert_array_equal(np.asarray(out.payload["w"]),
                                  np.arange(8, dtype=np.float32))
    with pytest.raises(ValueError, match="already registered"):
        pl.register_stage(name)(pl.Stage)


# ---------------------------------------------------------------------------
# legacy FilterChain shim equivalence
# ---------------------------------------------------------------------------

def _lsq_executor(name, seed, w_true, n=128, lr=0.3, local_steps=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, w_true.size)).astype(np.float32)
    y = X @ w_true

    def train_fn(params, rnd):
        w = np.asarray(params["w"]).copy()
        for _ in range(local_steps):
            w = w - lr * (X.T @ (X @ w - y) / n)
        return {"w": w}, n, {}

    return TrainExecutor(name, train_fn)


@pytest.mark.parametrize("transmission", ["regular", "container"])
def test_filterchain_shim_matches_pipeline_bitwise(transmission):
    """The deprecated Filter/FilterChain configuration, adapted through
    the shim, trains to bitwise-identical weights as the equivalent
    per-item pipeline — the API redesign changes where transforms run,
    not what they compute."""
    w_true = np.arange(1, 9, dtype=np.float32) / 8.0

    def run(wire_kwargs):
        sim = FLSimulator(
            [_lsq_executor(f"site-{i}", i, w_true) for i in range(3)],
            FedAvgAggregator(),
            SimulationConfig(num_rounds=6, transmission=transmission, chunk_size=2048),
            **wire_kwargs,
        )
        return sim.run({"w": np.zeros(8, np.float32)})

    filters = two_way_quantization("blockwise8")
    legacy = run({"server_filters": filters, "client_filters": filters})
    stack = ["quantize:blockwise8"]
    new = run({"pipelines": {"task_data": stack, "task_result": stack}})
    np.testing.assert_array_equal(np.asarray(legacy["w"]), np.asarray(new["w"]))


def test_legacy_filters_and_pipelines_are_mutually_exclusive():
    with pytest.raises(ValueError, match="not both"):
        FLSimulator(
            [_lsq_executor("s0", 0, np.ones(4, np.float32))],
            FedAvgAggregator(),
            SimulationConfig(),
            server_filters=no_filters(),
            pipelines={"task_data": []},
        )


# ---------------------------------------------------------------------------
# acceptance: peak transmission memory is O(largest item) with quantization
# ---------------------------------------------------------------------------

def test_container_quantized_peak_is_largest_item_not_whole_payload():
    """The tentpole claim: with container streaming and an nf4 quantize
    *stage*, peak transmission memory is bounded by ~one (quantized)
    item; the legacy filter path materializes the whole quantized
    payload before streaming and is metered accordingly."""
    sd = {f"layer.{i}": np.random.default_rng(i).standard_normal((128, 128))
          .astype(np.float32) for i in range(16)}  # 1 MiB total, 64 KiB items
    q_item = quantize(next(iter(sd.values())), "nf4").total_bytes
    q_total = sum(quantize(v, "nf4").total_bytes for v in sd.values())

    def train_fn(params, rnd):
        return {k: np.asarray(v) for k, v in params.items()}, 1, {}

    def run(wire_kwargs):
        sim = FLSimulator(
            [TrainExecutor("s0", train_fn)],
            FedAvgAggregator(),
            SimulationConfig(num_rounds=1, transmission="container", chunk_size=4096),
            **wire_kwargs,
        )
        sim.run(dict(sd))
        return sim.meter.peak

    stack = ["quantize:nf4"]
    peak_pipeline = run({"pipelines": {"task_data": stack, "task_result": stack}})
    filters = two_way_quantization("nf4")
    peak_legacy = run({"server_filters": filters, "client_filters": filters})

    # pipeline: ~one quantized item live on each side of the loopback
    assert peak_pipeline <= 4 * (q_item + 8192)
    assert peak_pipeline < q_total / 2
    # legacy shim: the whole quantized payload is materialized first
    assert peak_legacy >= q_total
    assert peak_pipeline < peak_legacy / 2


# ---------------------------------------------------------------------------
# honest wire accounting
# ---------------------------------------------------------------------------

def test_traffic_stats_count_true_bytes_on_wire():
    """bytes_sent includes frame headers, envelopes and the transmitted
    message-header item — strictly more than the tensor payload; with a
    compression stage on compressible data, strictly (and hugely) less.
    """
    sd = {"w": np.zeros((1 << 15,), np.float32)}  # 128 KiB of zeros

    def train_fn(params, rnd):
        return {k: np.asarray(v) for k, v in params.items()}, 1, {}

    def run(stack):
        sim = FLSimulator(
            [TrainExecutor("s0", train_fn)], FedAvgAggregator(),
            SimulationConfig(num_rounds=1, chunk_size=4096),
            pipelines={"task_data": stack, "task_result": stack},
        )
        sim.run(dict(sd))
        return sim.stats

    plain = run([])
    assert plain.bytes_sent > plain.payload_bytes > 0  # framing overhead counted
    zipped = run(["zlib"])
    assert zipped.payload_bytes == plain.payload_bytes
    assert zipped.bytes_sent < plain.payload_bytes / 20  # honest compression ratio


# ---------------------------------------------------------------------------
# chunk-level fault injection end-to-end (scheduler wire)
# ---------------------------------------------------------------------------

def test_chunk_faults_retransmit_and_lengthen_simulated_time():
    """LossyDriver + ReliableTransfer run inside the scheduler wire:
    payloads survive bit-exactly, retransmitted chunks are counted, and
    the extra bytes feed back into simulated transfer time."""
    w_true = np.arange(1, 5, dtype=np.float32)
    net = NetworkModel(default=LinkProfile("slow", bandwidth_mbps=4.0, latency_ms=5.0))

    def run(**cfg_kwargs):
        sim = FLSimulator(
            [_lsq_executor(f"site-{i}", i, w_true) for i in range(2)],
            FedAvgAggregator(),
            SimulationConfig(num_rounds=3, chunk_size=256, **cfg_kwargs),
            pipelines={"task_data": [], "task_result": ["crc32"]},
            runtime=RuntimeConfig(seed=0),
            network=net,
        )
        final = sim.run({"w": np.zeros(4, np.float32)})
        return final, sim

    clean, sim_clean = run()
    lossy, sim_lossy = run(chunk_drop_prob=0.25, chunk_dup_prob=0.05,
                           chunk_reorder_window=3, fault_seed=7)
    # exact reassembly: the lossy federation trains identically
    np.testing.assert_array_equal(np.asarray(clean["w"]), np.asarray(lossy["w"]))
    assert sim_lossy.stats.retransmits > 0
    assert sim_lossy.stats.bytes_sent > sim_clean.stats.bytes_sent
    # retransmitted bytes feed the network model -> longer simulated rounds
    assert sim_lossy.sim_time_s > sim_clean.sim_time_s


def test_chunk_faults_are_deterministic():
    w_true = np.arange(1, 5, dtype=np.float32)

    def run():
        sim = FLSimulator(
            [_lsq_executor("site-0", 0, w_true)], FedAvgAggregator(),
            SimulationConfig(num_rounds=2, chunk_size=128, chunk_drop_prob=0.3,
                             fault_seed=3),
            runtime=RuntimeConfig(seed=1),
        )
        final = sim.run({"w": np.zeros(4, np.float32)})
        return np.asarray(final["w"]), sim.stats.bytes_sent, sim.stats.retransmits

    w1, b1, r1 = run()
    w2, b2, r2 = run()
    np.testing.assert_array_equal(w1, w2)
    assert (b1, r1) == (b2, r2) and r1 > 0


def test_chunk_faults_rejected_over_tcp():
    with pytest.raises(ValueError, match="tcp"):
        FLSimulator(
            [_lsq_executor("s0", 0, np.ones(4, np.float32))],
            FedAvgAggregator(),
            SimulationConfig(driver="tcp", chunk_drop_prob=0.1),
        )


def test_unknown_driver_name_raises():
    with pytest.raises(ValueError, match="unknown driver"):
        FLSimulator(
            [_lsq_executor("s0", 0, np.ones(4, np.float32))],
            FedAvgAggregator(),
            SimulationConfig(driver="quic"),
        ).run({"w": np.zeros(4, np.float32)})


# ---------------------------------------------------------------------------
# delta / topk / zstd stages + quantize rules (ISSUE 4 satellites)
# ---------------------------------------------------------------------------

def test_delta_stage_transmits_residuals_and_reconstructs():
    """Round r ships x_r - base_{r-1}; the decoder reconstructs each x_r
    to one float32 rounding (the encoder tracks the decoder's
    reconstruction, so the error never accumulates across rounds), and
    the envelope meta tracks the stream position."""
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((64,)).astype(np.float32) for _ in range(8)]
    p = pl.WirePipeline([pl.build_stage("delta")])
    for i, x in enumerate(xs):
        msg, ctx = p.begin_encode(_msg({"w": x.copy()}, client="site-0"))
        blob = p.encode_wire_item("w", msg.payload["w"], ctx)
        (hlen,) = struct.unpack_from("<I", blob, 0)
        header = json.loads(blob[4:4 + hlen])
        assert header["v"] == ["delta"]
        assert header["vm"][0]["d"] == i          # stream position on the wire
        assert header["vm"][0].get("full", 0) == (1 if i == 0 else 0)
        name, value, _ = p.decoder().decode_item(blob)
        np.testing.assert_allclose(np.asarray(value), x, rtol=1e-6, atol=1e-6)


def test_delta_stage_near_converged_rounds_compress_away():
    """The point of delta encoding: once the model stops moving, the
    residual is all zeros and zlib collapses it."""
    x = np.linspace(-1, 1, 1 << 14).astype(np.float32)
    p = pl.WirePipeline([pl.build_stage("delta"), pl.build_stage("zlib")])

    def wire_len(arr):
        msg, ctx = p.begin_encode(_msg({"w": arr.copy()}, client="c"))
        blob = p.encode_wire_item("w", msg.payload["w"], ctx)
        name, value, _ = p.decoder().decode_item(blob)
        np.testing.assert_array_equal(np.asarray(value), arr)
        return len(blob)

    first = wire_len(x)
    repeat = wire_len(x)  # unchanged payload => zero residual
    assert repeat < first / 100


def test_delta_stage_residual_streams_are_per_client():
    x = np.ones((32,), np.float32)
    p = pl.WirePipeline([pl.build_stage("delta")])

    def roundtrip(client):
        msg, ctx = p.begin_encode(_msg({"w": x.copy()}, client=client))
        blob = p.encode_wire_item("w", msg.payload["w"], ctx)
        (hlen,) = struct.unpack_from("<I", blob, 0)
        return json.loads(blob[4:4 + hlen])["vm"][0]

    assert roundtrip("site-a") == {"d": 0, "full": 1}
    assert roundtrip("site-b") == {"d": 0, "full": 1}  # b starts fresh
    assert roundtrip("site-a")["d"] == 1


def test_delta_stage_desynchronized_receiver_fails_loudly():
    x = np.ones((16,), np.float32)
    sender = pl.WirePipeline([pl.build_stage("delta")])
    for _ in range(2):
        msg, ctx = sender.begin_encode(_msg({"w": x.copy()}, client="c"))
        blob = sender.encode_wire_item("w", msg.payload["w"], ctx)
    # a fresh receiver (registry fallback) is at position 0, wire says 1
    with pytest.raises(pl.WireIntegrityError, match="out of sync"):
        pl.build_pipeline([]).decoder().decode_item(blob)


def test_topk_stage_roundtrip_and_sparse_golden_serialization():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1 << 12,)).astype(np.float32)
    p = pl.build_pipeline(["topk:0.05"])
    msg, ctx = p.begin_encode(_msg({"w": x.copy()}))
    blob = p.encode_wire_item("w", msg.payload["w"], ctx)
    (hlen,) = struct.unpack_from("<I", blob, 0)
    header = json.loads(blob[4:4 + hlen])
    k = int(np.ceil(0.05 * x.size))
    assert header["vm"][0] == {"k": k, "n": x.size}
    assert len(blob) < x.nbytes / 2  # indices+values beat dense
    name, value, _ = p.decoder().decode_item(blob)
    dense = np.asarray(value)
    kept = np.flatnonzero(dense)
    assert len(kept) == k
    np.testing.assert_array_equal(dense[kept], x[kept])  # survivors exact
    # the k largest |x| all survived
    assert np.min(np.abs(x[kept])) >= np.max(np.abs(np.delete(x, kept)))


def test_topk_sparse_tensor_inner_codec_roundtrip():
    from repro.core.serialization import deserialize_item, serialize_item
    from repro.core.sparse import topk_sparsify

    x = np.arange(-8, 8, dtype=np.float32).reshape(4, 4)
    sp = topk_sparsify(x, 0.25)
    name, back, consumed = deserialize_item(serialize_item("w", sp))
    assert name == "w" and consumed == len(serialize_item("w", sp))
    np.testing.assert_array_equal(back.to_dense(), sp.to_dense())
    assert back.orig_shape == (4, 4)


def test_topk_small_tensors_pass_dense():
    p = pl.build_pipeline([{"stage": "topk", "fraction": 0.1, "min_params": 64}])
    out = _roundtrip(p, _msg({"bias": np.arange(8, dtype=np.float32)}))
    np.testing.assert_array_equal(np.asarray(out.payload["bias"]),
                                  np.arange(8, dtype=np.float32))


def test_zstd_stage_registered_only_when_importable():
    try:
        import zstandard  # noqa: F401
        available = True
    except ImportError:
        available = False
    assert ("zstd" in pl.registered_stages()) == available


def test_zstd_stage_roundtrip_when_available():
    pytest.importorskip("zstandard")
    m = _msg({"w": np.zeros((1 << 14,), np.float32)})
    p = pl.build_pipeline(["zstd:5"])
    msg, ctx = p.begin_encode(m)
    blob = p.encode_wire_item("w", msg.payload["w"], ctx)
    assert len(blob) < m.payload["w"].nbytes / 50
    out = _roundtrip(p, m)
    np.testing.assert_array_equal(np.asarray(out.payload["w"]), m.payload["w"])


def test_quantize_rules_per_layer_precision():
    """The SelectiveQuantizeFilter policy as a stage: first matching
    substring rule decides each tensor's format, default covers the
    rest, "keep" pins original precision."""
    rng = np.random.default_rng(5)
    payload = {
        "embed.w": rng.standard_normal((64,)).astype(np.float32),
        "layers.0.norm": rng.standard_normal((64,)).astype(np.float32),
        "layers.0.mlp": rng.standard_normal((64,)).astype(np.float32),
    }
    p = pl.build_pipeline(["quantize:norm=fp16,embed=keep,nf4"])
    msg, ctx = p.begin_encode(_msg(dict(payload)))
    assert msg.headers["quantized_fmt"] == "mixed:fp16,nf4"
    fmts = {}
    for name, value in msg.payload.items():
        enc = p.stages[0].encode_item(name, value, ctx)
        fmts[name] = enc.fmt if isinstance(enc, QuantizedTensor) else "keep"
    assert fmts == {"embed.w": "keep", "layers.0.norm": "fp16",
                    "layers.0.mlp": "nf4"}
    out = _roundtrip(p, _msg(dict(payload)))
    np.testing.assert_array_equal(np.asarray(out.payload["embed.w"]),
                                  payload["embed.w"])  # kept bit-exact
    np.testing.assert_allclose(np.asarray(out.payload["layers.0.norm"]),
                               payload["layers.0.norm"], atol=1e-3)
    np.testing.assert_allclose(np.asarray(out.payload["layers.0.mlp"]),
                               payload["layers.0.mlp"], atol=0.6)


def test_quantize_rules_dict_spec_matches_selective_filter():
    from repro.core.filters import SelectiveQuantizeFilter

    rng = np.random.default_rng(6)
    payload = {"a.norm": rng.standard_normal((128,)).astype(np.float32),
               "b.body": rng.standard_normal((128,)).astype(np.float32)}
    stage_out = _roundtrip(
        pl.build_pipeline([{"stage": "quantize",
                            "rules": [["norm", "fp16"]], "fmt": "blockwise8"}]),
        _msg(dict(payload)))
    filt = SelectiveQuantizeFilter([("norm", "fp16")], default_fmt="blockwise8")
    from repro.core.filters import DequantizeFilter
    filter_out = DequantizeFilter().process(filt.process(_msg(dict(payload))))
    for k in payload:
        np.testing.assert_array_equal(np.asarray(stage_out.payload[k]),
                                      np.asarray(filter_out.payload[k]))


def test_quantize_stage_requires_fmt_or_rules():
    with pytest.raises(ValueError, match="format and/or rules"):
        pl.build_pipeline([{"stage": "quantize"}])


def test_zstd_stage_oversize_stream_raises_wire_integrity_error():
    pytest.importorskip("zstandard")
    p = pl.build_pipeline(["zstd"])
    m = _msg({"w": np.zeros((4096,), np.float32)})
    msg, ctx = p.begin_encode(m)
    blob = p.encode_wire_item("w", msg.payload["w"], ctx)
    (hlen,) = struct.unpack_from("<I", blob, 0)
    header = json.loads(blob[4:4 + hlen])
    header["b"][0][1]["n"] //= 2  # declare half the true original length
    hb = json.dumps(header, sort_keys=True).encode()
    tampered = struct.pack("<I", len(hb)) + hb + blob[4 + hlen:]
    with pytest.raises(pl.WireIntegrityError, match="declared length"):
        p.decoder().decode_item(tampered)


def test_delta_stage_residual_without_base_raises_wire_error():
    """A forged/corrupted envelope claiming position 0 but no 'full'
    snapshot must surface as a wire-integrity fault, not a KeyError."""
    x = np.ones((16,), np.float32)
    sender = pl.WirePipeline([pl.build_stage("delta")])
    msg, ctx = sender.begin_encode(_msg({"w": x}, client="c"))
    blob = sender.encode_wire_item("w", msg.payload["w"], ctx)
    (hlen,) = struct.unpack_from("<I", blob, 0)
    header = json.loads(blob[4:4 + hlen])
    del header["vm"][0]["full"]  # lie: claim this is a residual
    hb = json.dumps(header, sort_keys=True).encode()
    tampered = struct.pack("<I", len(hb)) + hb + blob[4 + hlen:]
    with pytest.raises(pl.WireIntegrityError, match="no base"):
        pl.build_pipeline([]).decoder().decode_item(tampered)


def test_quantize_rules_reject_two_bare_defaults():
    with pytest.raises(ValueError, match="two default"):
        pl.build_pipeline(["quantize:norm=fp16,nf4,int8"])
