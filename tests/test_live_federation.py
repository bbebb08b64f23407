"""The live multi-process federation plane (ISSUE 7 tentpole).

Fast tests run the real :class:`FederationServer` over localhost TCP
with in-process :class:`FederationClient` threads driving deterministic
numpy executors — real sockets, real protocol, no jax subprocess cost:

* live rounds produce weights **bitwise-equal** to :class:`FLSimulator`
  on the same executors/pipeline stack (ordered uplink);
* the handshake fails fast: pipeline-fingerprint mismatch, stale round
  epoch, unknown and duplicate client names are all rejected *before*
  any fold;
* a client killed mid-uplink contributes exactly zero weight — the
  poisoned fold restarts over the survivors and the round completes;
* a crashed client can rejoin at the server's current epoch and
  participates in later rounds;
* the concurrent uplink mode completes and agrees numerically.

One slow-marked test runs the full subprocess path (`run_live_federation`
spawning real `python -m repro.launch.federation` clients) against
``run_job`` — the same check the `live-smoke` CI job performs on every
push.
"""
import socket
import threading
import time

import numpy as np
import pytest

from repro.core import streaming as sm
from repro.core.messages import Message, MessageKind
from repro.fl import FedAvgAggregator, FLSimulator, SimulationConfig, TrainExecutor
from repro.fl.controller import make_task
from repro.launch.federation import (
    PROTO,
    FederationClient,
    FederationServer,
    aggregator_spec,
    build_pipelines_from_spec,
    live_spec,
    pipeline_fingerprint,
    weights_bitwise_equal,
)

W_TRUE = np.arange(1, 9, dtype=np.float32) / 8.0
STACK = ["quantize:blockwise8", "crc32"]


def _lsq_executor(name, seed, w_true=W_TRUE, n=128, lr=0.3, local_steps=3,
                  sleep_s=0.0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, w_true.size)).astype(np.float32)
    y = X @ w_true

    def train_fn(params, rnd):
        if sleep_s:
            time.sleep(sleep_s)
        w = np.asarray(params["w"]).copy()
        for _ in range(local_steps):
            w = w - lr * (X.T @ (X @ w - y) / n)
        return {"w": w}, n, {}

    return TrainExecutor(name, train_fn)


def _spec(clients=3, rounds=2, stack=STACK):
    return {"clients": clients, "rounds": rounds, "chunk_mb": 1,
            "pipeline": {"task_data": list(stack),
                         "task_result": list(stack)}}


def _start_clients(server, executors, **kwargs):
    """In-process FederationClients on threads; returns (threads, errors)."""
    pipelines = build_pipelines_from_spec(server.spec)
    errors = []
    threads = []
    for ex in executors:
        client = FederationClient(
            name=ex.name, executor=ex, pipelines=pipelines,
            address=server.address, fingerprint=server.fingerprint,
            timeout_s=60.0, **kwargs,
        )

        def run(c=client):
            try:
                c.run()
            except Exception as exc:  # noqa: BLE001 - surfaced by the test
                errors.append(exc)

        t = threading.Thread(target=run, daemon=True, name=f"live-{ex.name}")
        t.start()
        threads.append(t)
    return threads, errors


def _join(threads, timeout=60):
    for t in threads:
        t.join(timeout=timeout)
        assert not t.is_alive(), "client thread wedged"


INIT = {"w": np.zeros(8, np.float32)}


# ---------------------------------------------------------------------------
# live == sim, bitwise
# ---------------------------------------------------------------------------

def test_live_ordered_rounds_bitwise_match_simulator():
    """Real TCP rounds with grant-ordered uplink folds execute the exact
    arithmetic of the sequential simulator — bitwise-equal weights."""
    spec = _spec(clients=3, rounds=2)
    server = FederationServer(spec, join_timeout_s=30).start()
    try:
        threads, errors = _start_clients(
            server, [_lsq_executor(f"site-{i}", i) for i in range(3)])
        live = server.run(dict(INIT))
        _join(threads)
        assert not errors
    finally:
        server.close()

    sim = FLSimulator(
        [_lsq_executor(f"site-{i}", i) for i in range(3)],
        FedAvgAggregator(),
        SimulationConfig(num_rounds=2, transmission="container"),
        pipelines={"task_data": list(STACK), "task_result": list(STACK)},
        server_streaming_agg=True,
    )
    expected = sim.run(dict(INIT))
    assert weights_bitwise_equal(live, expected)
    assert [r["clients"] for r in server.round_log] == [
        ["site-0", "site-1", "site-2"]] * 2
    assert server.restarts == 0 and server.bytes_up > 0 and server.bytes_down > 0
    assert server.platforms == {"server": "cpu", "site-0": "cpu",
                                "site-1": "cpu", "site-2": "cpu"}


def test_spawned_clients_are_pinned_off_the_chip(monkeypatch):
    """The launcher's process owns the accelerator; every client process
    it spawns runs on the CPU backend, whatever the parent selected."""
    from repro.launch.federation import _client_env

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = _client_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "src" in env["PYTHONPATH"]


def test_live_concurrent_uplink_completes_and_agrees():
    """Throughput mode: all uplinks fold at once from per-connection
    threads; fold order is scheduler-dependent so equality is numerical,
    not bitwise."""
    spec = _spec(clients=3, rounds=2)
    server = FederationServer(spec, uplink="concurrent", join_timeout_s=30).start()
    try:
        threads, errors = _start_clients(
            server, [_lsq_executor(f"site-{i}", i) for i in range(3)])
        live = server.run(dict(INIT))
        _join(threads)
        assert not errors
    finally:
        server.close()
    sim = FLSimulator(
        [_lsq_executor(f"site-{i}", i) for i in range(3)],
        FedAvgAggregator(),
        SimulationConfig(num_rounds=2, transmission="container"),
        pipelines={"task_data": list(STACK), "task_result": list(STACK)},
        server_streaming_agg=True,
    )
    expected = sim.run(dict(INIT))
    np.testing.assert_allclose(np.asarray(live["w"]),
                               np.asarray(expected["w"]), rtol=1e-5)


# ---------------------------------------------------------------------------
# handshake: fail fast, never mid-fold
# ---------------------------------------------------------------------------

def _hello(server, **over):
    """One raw handshake against a running server; returns the reply."""
    conn = sm.Connection(socket.create_connection(server.address))
    try:
        msg = {"type": "hello", "client": "site-0", "epoch": 0,
               "proto": PROTO, "fingerprint": server.fingerprint}
        msg.update(over)
        conn.send_ctrl(msg)
        return conn.recv_ctrl()
    finally:
        conn.close()


def test_handshake_rejects_fingerprint_mismatch():
    server = FederationServer(_spec()).start()
    try:
        other = build_pipelines_from_spec(_spec(stack=["zlib"]))
        wrong = pipeline_fingerprint(other, aggregator_spec(_spec(stack=["zlib"])))
        assert wrong != server.fingerprint
        resp = _hello(server, fingerprint=wrong)
        assert resp["type"] == "reject"
        assert "fingerprint mismatch" in resp["reason"]
    finally:
        server.close()


def test_handshake_rejects_stale_epoch_unknown_and_duplicate():
    server = FederationServer(_spec(clients=2)).start()
    try:
        resp = _hello(server, epoch=5)
        assert resp["type"] == "reject" and "stale round epoch" in resp["reason"]
        resp = _hello(server, client="site-9")
        assert resp["type"] == "reject" and "unknown client" in resp["reason"]
        resp = _hello(server, proto=99)
        assert resp["type"] == "reject" and "protocol revision" in resp["reason"]
        # first site-0 join holds its slot; a second hello for the same
        # name must bounce instead of hijacking the connection
        held = sm.Connection(socket.create_connection(server.address))
        try:
            held.send_ctrl({"type": "hello", "client": "site-0", "epoch": 0,
                            "proto": PROTO, "fingerprint": server.fingerprint})
            assert held.recv_ctrl()["type"] == "welcome"
            resp = _hello(server)
            assert resp["type"] == "reject" and "duplicate" in resp["reason"]
        finally:
            held.close()
    finally:
        server.close()


def test_client_raises_on_rejection():
    server = FederationServer(_spec()).start()
    try:
        bad = FederationClient(
            name="site-0", executor=_lsq_executor("site-0", 0),
            pipelines=build_pipelines_from_spec(_spec(stack=["zlib"])),
            address=server.address, fingerprint="0" * 16,
        )
        with pytest.raises(RuntimeError, match="fingerprint mismatch"):
            bad.run()
    finally:
        server.close()


# ---------------------------------------------------------------------------
# crash mid-uplink: zero phantom weight, the round completes
# ---------------------------------------------------------------------------

def _expected_rounds(executors, rounds, init):
    """Reference arithmetic: the sequential batch fold (which the
    streaming plane matches bitwise by construction)."""
    agg = FedAvgAggregator()
    w = dict(init)
    for rnd in range(rounds):
        for ex in executors:
            agg.accept(ex.execute(make_task(rnd, w)))
        w = agg.finish()
    return w


def _encode_result_frames(pipelines, name, payload):
    pipeline = pipelines["task_result"]
    msg = Message(MessageKind.TASK_RESULT, dict(payload),
                  {"round": 0, "client": name, "num_samples": 128})
    enc, ctx = pipeline.begin_encode(msg)

    frames = []

    class _Cap:
        def send(self, chunk):
            frames.append(chunk.encode())

    sm.ContainerStreamer(_Cap(), 1 << 20).send_items(
        pipeline.iter_encode_views(enc, ctx), pipeline.n_items(enc))
    return frames


def test_client_killed_mid_uplink_contributes_zero_weight():
    """The saboteur handshakes, trains 'successfully', then dies after
    shipping its meta item and one payload item — its sample weight and
    partial fold are already in the running sums, so the server must
    discard that fold and restart with the survivors. Final weights are
    exactly the survivors-only aggregate: zero phantom weight."""
    spec = _spec(clients=3, rounds=2, stack=[])  # identity pipelines:
    # the reference arithmetic below doesn't re-implement quantization
    server = FederationServer(spec, join_timeout_s=30,
                              round_timeout_s=30).start()
    pipelines = build_pipelines_from_spec(spec)

    def saboteur():
        conn = sm.Connection(socket.create_connection(server.address))
        try:
            conn.send_ctrl({"type": "hello", "client": "site-2", "epoch": 0,
                            "proto": PROTO, "fingerprint": server.fingerprint})
            assert conn.recv_ctrl()["type"] == "welcome"
            assert conn.recv_ctrl()["type"] == "task"
            conn.recv_stream(lambda c: None)
            assert conn.recv_ctrl()["type"] == "grant"
            conn.send_ctrl({"type": "result", "round": 0, "client": "site-2"})
            frames = _encode_result_frames(
                pipelines, "site-2",
                {"a": np.full(8, 100.0, np.float32),
                 "w": np.full(8, 100.0, np.float32)})
            # meta + first payload item reach the fold, then the socket
            # dies mid-stream — worst case: weight already registered
            conn.sock.sendall(frames[0] + frames[1])
        finally:
            conn.close()

    try:
        survivors = [_lsq_executor(f"site-{i}", i) for i in range(2)]
        threads, errors = _start_clients(server, survivors)
        sab = threading.Thread(target=saboteur, daemon=True)
        sab.start()
        live = server.run(dict(INIT))
        _join(threads)
        sab.join(timeout=30)
        assert not errors
    finally:
        server.close()

    expected = _expected_rounds(
        [_lsq_executor(f"site-{i}", i) for i in range(2)], 2, INIT)
    assert weights_bitwise_equal(live, expected)
    assert server.restarts == 1
    # round 0 completed with exactly the survivors' weight in it
    assert server.round_log[0]["clients"] == ["site-0", "site-1"]
    assert server.round_log[1]["clients"] == ["site-0", "site-1"]
    assert "a" not in live  # the poisoned fold's items are gone wholesale


def test_crashed_client_rejoins_at_current_epoch():
    """site-2 dies after round 0, then reconnects presenting the
    server's *current* round epoch: accepted, and folded into every
    round after its rejoin."""
    spec = _spec(clients=3, rounds=5, stack=[])
    server = FederationServer(spec, join_timeout_s=30,
                              round_timeout_s=30).start()
    pipelines = build_pipelines_from_spec(spec)

    def die_after_round0():
        conn = sm.Connection(socket.create_connection(server.address))
        try:
            conn.send_ctrl({"type": "hello", "client": "site-2", "epoch": 0,
                            "proto": PROTO, "fingerprint": server.fingerprint})
            assert conn.recv_ctrl()["type"] == "welcome"
            assert conn.recv_ctrl()["type"] == "task"
            conn.recv_stream(lambda c: None)
            assert conn.recv_ctrl()["type"] == "grant"
            conn.send_ctrl({"type": "result", "round": 0, "client": "site-2"})
            for f in _encode_result_frames(
                    pipelines, "site-2", {"w": np.zeros(8, np.float32)}):
                conn.sock.sendall(f)
        finally:
            conn.close()  # gone before round 1's downlink

    rejoined = threading.Event()

    def rejoin():
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            client = FederationClient(
                name="site-2",
                executor=_lsq_executor("site-2", 2),
                pipelines=pipelines, address=server.address,
                fingerprint=server.fingerprint,
                epoch=server.current_round, timeout_s=60.0,
            )
            try:
                client.run()
            except (RuntimeError, OSError, ConnectionError):
                time.sleep(0.02)  # raced a round boundary; re-poll epoch
                continue
            rejoined.set()
            return

    try:
        threads, errors = _start_clients(
            server,
            [_lsq_executor(f"site-{i}", i, sleep_s=0.15) for i in range(2)])
        t_dead = threading.Thread(target=die_after_round0, daemon=True)
        t_dead.start()
        # the doomed connection must hold site-2's roster slot before the
        # rejoin loop starts, so its early attempts bounce as duplicates
        # instead of stealing round 0
        server.wait_for_clients()
        t_rejoin = threading.Thread(target=rejoin, daemon=True)
        t_rejoin.start()
        live = server.run(dict(INIT))
        _join(threads)
        t_dead.join(timeout=30)
        t_rejoin.join(timeout=30)
        assert not errors
    finally:
        server.close()

    assert rejoined.is_set()
    assert server.round_log[0]["clients"] == ["site-0", "site-1", "site-2"]
    # the crash costs at least one survivor-only round...
    assert any(r["clients"] == ["site-0", "site-1"] for r in server.round_log)
    # ...and the rejoin puts site-2 back into a later round's fold
    assert server.round_log[-1]["clients"] == ["site-0", "site-1", "site-2"]
    assert np.isfinite(np.asarray(live["w"])).all()


# ---------------------------------------------------------------------------
# live_spec validation
# ---------------------------------------------------------------------------

def test_live_spec_rejects_sim_only_surface():
    with pytest.raises(ValueError, match="runtime"):
        live_spec({"clients": 2, "runtime": {"policy": "fedasync"}})
    with pytest.raises(ValueError, match="legacy"):
        live_spec({"clients": 2, "quantization": {"fmt": "nf4"}})
    with pytest.raises(ValueError, match="stateless"):
        live_spec({"clients": 2,
                   "pipeline": {"task_result": ["ef-quantize:nf4"]}})
    with pytest.raises(ValueError, match="at least one client"):
        live_spec({"clients": 0})
    with pytest.raises(ValueError, match="uplink mode"):
        FederationServer(_spec(), uplink="sideways")


def test_fingerprint_tracks_stack_and_aggregator():
    base = _spec()
    fp = pipeline_fingerprint(build_pipelines_from_spec(base),
                              aggregator_spec(base))
    assert fp == pipeline_fingerprint(build_pipelines_from_spec(_spec()),
                                      aggregator_spec(_spec()))
    other = _spec(stack=["zlib"])
    assert fp != pipeline_fingerprint(build_pipelines_from_spec(other),
                                      aggregator_spec(other))
    agg_differs = dict(base, aggregator="quantized-fedavg")
    assert fp != pipeline_fingerprint(build_pipelines_from_spec(agg_differs),
                                      aggregator_spec(agg_differs))


# ---------------------------------------------------------------------------
# the real thing: subprocess clients, jax model, sim equality
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_subprocess_federation_bitwise_matches_run_job():
    from repro.fl.job import run_job
    from repro.launch.federation import run_live_federation

    spec = {
        "arch": "llama3.2-1b", "smoke": True, "rounds": 2, "clients": 2,
        "local_steps": 1, "batch": 2, "seq": 16,
        "pipeline": {"task_result_out": ["quantize:blockwise8", "crc32"]},
        "server_streaming_agg": True,
    }
    live = run_live_federation(spec)
    assert live["client_exit_codes"] == [0, 0]
    assert live["platforms"] == {"server": "cpu", "site-0": "cpu", "site-1": "cpu"}
    sim = run_job(dict(spec))
    assert weights_bitwise_equal(live["final_weights"], sim["final_weights"])
