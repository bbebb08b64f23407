"""Unified telemetry plane: tracer, metrics registry, round forensics.

Covers the observability contract end to end:

* the :class:`Tracer` flight recorder exports **valid Chrome
  trace-event JSON** (schema asserted by ``validate_chrome_trace``, the
  same check CI runs over the nightly artifact), with the two clocks as
  two Perfetto processes;
* the ring buffer bounds memory and reports drops;
* the metrics registry's labeled series and JSON-safe snapshots, plus
  the ``as_dict()`` exports the simulator publishes into it;
* **trace neutrality** — the load-bearing invariant: turning tracing on
  must not move a single simulated event or flip a single weight bit,
  sequential and async alike.
"""
import json

import numpy as np
import pytest

from repro.obs import MetricsRegistry, Tracer, validate_chrome_trace
from repro.obs import trace as obs_trace
from repro.obs.metrics import _series_key
from repro.utils.mem import MemoryMeter


# ---------------------------------------------------------------------------
# tracer basics + export schema
# ---------------------------------------------------------------------------

def test_tracer_exports_valid_dual_clock_trace():
    tr = Tracer()
    with tr.span("outer", "test", round=0):
        with tr.span("inner", "test", item="w"):
            pass
    with tr.span("mark", "test", seq=1):
        pass
    with tr.span("depth", "test", nbytes=3):
        pass
    tr.sim_span("uplink", 1.0, 2.5, track="site-0", wire_bytes=64)
    tr.sim_instant("arrival", 2.5, track="site-0")
    tr.sim_counter("queue_depth", 2.5, 4)
    obj = tr.chrome_trace()
    assert validate_chrome_trace(obj) == len(obj["traceEvents"])
    json.dumps(obj)  # the whole export is JSON-safe

    by_pid = {}
    for ev in obj["traceEvents"]:
        by_pid.setdefault(ev["pid"], set()).add(ev["name"])
    # wall clock and simulated time are two separate Perfetto processes
    assert {"outer", "inner", "mark", "depth"} <= by_pid[obs_trace.PID_WALL]
    assert {"uplink", "arrival", "queue_depth"} <= by_pid[obs_trace.PID_SIM]
    # process/thread metadata names both clocks for the viewer
    procs = {ev["pid"]: ev["args"]["name"] for ev in obj["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "process_name"}
    assert procs == {obs_trace.PID_WALL: "wall clock",
                     obs_trace.PID_SIM: "simulated time"}
    tracks = {ev["args"]["name"] for ev in obj["traceEvents"]
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert "site-0" in tracks
    # sim timestamps are simulated seconds in microseconds
    up = next(ev for ev in obj["traceEvents"] if ev["name"] == "uplink")
    assert up["ts"] == pytest.approx(1.0e6) and up["dur"] == pytest.approx(1.5e6)


def test_span_args_attach_by_reference_for_late_byte_counts():
    tr = Tracer()
    with tr.span("encode", "wire", item="w") as sp:
        sp.args["bytes_out"] = 1234
    ev = tr.chrome_trace()["traceEvents"][-1]
    assert ev["args"] == {"item": "w", "bytes_out": 1234}


def test_ring_buffer_bounds_memory_and_reports_drops():
    tr = Tracer(capacity=8)
    for i in range(50):
        with tr.span(f"e{2 * i}"):
            pass
        tr.sim_instant(f"e{2 * i + 1}", float(i), track="site-0")
    assert tr.total_events == 100 and tr.dropped == 92
    obj = tr.chrome_trace()
    assert validate_chrome_trace(obj)
    names = [ev["name"] for ev in obj["traceEvents"] if ev["ph"] in ("X", "i")]
    assert names == [f"e{i}" for i in range(92, 100)]  # newest win
    assert obj["otherData"]["dropped_events"] == 92


def test_tracer_rejects_nonpositive_capacity():
    with pytest.raises(ValueError, match="capacity"):
        Tracer(capacity=0)


def test_span_helper_is_shared_noop_when_inactive():
    assert obs_trace.ACTIVE is None
    cm1 = obs_trace.span("a", "b", k=1)
    cm2 = obs_trace.span("c")
    assert cm1 is cm2  # one shared no-op object, no per-call allocation
    with cm1:
        pass


def test_activate_installs_and_restores():
    tr = Tracer()
    assert obs_trace.ACTIVE is None
    with obs_trace.activate(tr):
        assert obs_trace.ACTIVE is tr
        with obs_trace.span("seen", "test"):
            pass
    assert obs_trace.ACTIVE is None
    assert [e["name"] for e in tr.chrome_trace()["traceEvents"]
            if e["ph"] == "X"] == ["seen"]


def test_sim_clock_stamps_wall_spans():
    tr = Tracer(sim_clock=lambda: 42.125)
    with tr.span("fold", "agg"):
        pass
    ev = tr.chrome_trace()["traceEvents"][-1]
    assert ev["args"]["sim_t"] == 42.125


@pytest.mark.parametrize("bad, why", [
    ([], "traceEvents"),
    ({"traceEvents": [{"ph": "Z", "name": "x", "pid": 1, "tid": 0}]}, "phase"),
    ({"traceEvents": [{"ph": "i", "pid": 1, "tid": 0, "ts": 0}]}, "name"),
    ({"traceEvents": [{"ph": "i", "name": "x", "pid": "1", "tid": 0,
                       "ts": 0}]}, "pid/tid"),
    ({"traceEvents": [{"ph": "i", "name": "x", "pid": 1, "tid": 0,
                       "ts": -5}]}, "timestamp"),
    ({"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 0,
                       "ts": 0}]}, "dur"),
    ({"traceEvents": [{"ph": "C", "name": "x", "pid": 1, "tid": 0,
                       "ts": 0, "args": {"v": "high"}}]}, "numeric"),
    ({"traceEvents": [{"ph": "M", "name": "process_name", "pid": 1,
                       "tid": 0, "args": {}}]}, "args.name"),
    ({"traceEvents": [{"ph": "i", "name": "x", "pid": 1, "tid": 0,
                       "ts": 0, "args": {"v": b"raw"}}]}, "serializable"),
])
def test_validate_chrome_trace_rejects(bad, why):
    with pytest.raises(ValueError, match=why):
        validate_chrome_trace(bad)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_series_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("wire.items", direction="up").inc()
    reg.counter("wire.items", direction="up").inc(4)
    reg.counter("wire.items", direction="down").inc()
    reg.gauge("queue").set(3)
    reg.gauge("queue").max(7)
    reg.gauge("queue").max(2)          # high watermark keeps 7
    for v in (1, 2, 3, 1024):
        reg.histogram("item_bytes").observe(v)
    snap = reg.snapshot()
    json.dumps(snap)
    assert snap["counters"]["wire.items{direction=up}"] == 5
    assert snap["counters"]["wire.items{direction=down}"] == 1
    assert snap["gauges"]["queue"] == 7
    h = snap["histograms"]["item_bytes"]
    assert h["count"] == 4 and h["min"] == 1 and h["max"] == 1024
    # bucket k counts [2^(k-1), 2^k): 1 -> b1, 2 and 3 -> b2, 1024 -> b11
    assert h["buckets"] == {"1": 1, "2": 2, "11": 1}


def test_series_key_sorts_labels():
    assert _series_key("m", {"b": 1, "a": 2}) == "m{a=2,b=1}"
    assert _series_key("m", {}) == "m"


def test_counter_is_monotone():
    with pytest.raises(ValueError, match="only go up"):
        MetricsRegistry().counter("c").inc(-1)


def test_registry_rejects_kind_mismatch():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered as Counter"):
        reg.gauge("x")


def test_publish_exports_numeric_values_only():
    reg = MetricsRegistry()
    reg.publish("traffic", {"messages": 4, "bytes": 2.5, "label": "up",
                            "ok": True}, client="site-0")
    g = reg.snapshot()["gauges"]
    assert g == {"traffic.messages{client=site-0}": 4,
                 "traffic.bytes{client=site-0}": 2.5}


# ---------------------------------------------------------------------------
# as_dict exports (what the simulator publishes into the registry)
# ---------------------------------------------------------------------------

def test_stats_as_dict_exports_are_json_safe():
    from repro.fl.simulator import TrafficStats
    from repro.runtime.scheduler import RuntimeStats

    t = TrafficStats()
    t.add(600, payload_nbytes=500)
    t.add(400, payload_nbytes=300, retransmits=1)
    td = t.as_dict()
    assert td == {"messages": 2, "bytes_sent": 1000,
                  "payload_bytes": 800, "retransmits": 1}

    rd = RuntimeStats(dispatches=5, completions=4, events_processed=33).as_dict()
    assert rd["dispatches"] == 5 and rd["events_processed"] == 33
    assert "queue_depth_peak" in rd

    m = MemoryMeter()
    m.alloc(100)
    m.copy(40)
    m.free(100)
    md = m.as_dict()
    assert md == {"live": 0, "peak": 100, "total_allocated": 100, "copied": 40}
    json.dumps({**td, **rd, **md})


# ---------------------------------------------------------------------------
# round forensics: one traced federation, both clocks attributable
# ---------------------------------------------------------------------------

def _job_spec(**over):
    spec = {
        "arch": "llama3.2-1b", "rounds": 2, "clients": 2, "local_steps": 1,
        "pipeline": {"task_result_out": ["quantize:nf4", "crc32"]},
        "server_streaming_agg": True,
    }
    spec.update(over)
    return spec


@pytest.mark.slow
def test_traced_job_exports_attributable_round_anatomy(tmp_path):
    from repro.fl.job import run_job

    out = str(tmp_path / "trace.json")
    result = run_job(_job_spec(
        trace=out,
        runtime={"policy": "sync",
                 "network": {"kind": "hetero", "tiers": ["fiber", "lte"]}},
    ))
    assert result["trace"]["path"] == out
    with open(out) as fh:
        obj = json.load(fh)
    assert validate_chrome_trace(obj) > 0

    wall = [e for e in obj["traceEvents"]
            if e["pid"] == obs_trace.PID_WALL and e["ph"] == "X"]
    wall_names = {e["name"] for e in wall}
    # every instrumented layer shows up on the wall clock
    assert {"wire.transmit", "wire.encode_item", "wire.decode_item",
            "stage.encode.quantize", "stage.encode.crc32",
            "stage.decode.quantize", "stage.decode.crc32",
            "kernel.quantize_batch", "agg.begin", "agg.accept_item",
            "agg.finish", "sched.settle"} <= wall_names
    # spans carry the attribution args round forensics needs
    tx = next(e for e in wall if e["name"] == "wire.transmit")
    assert tx["args"]["client"].startswith("site-") and "wire_bytes" in tx["args"]
    enc = next(e for e in wall if e["name"] == "wire.encode_item")
    assert "item" in enc["args"] and enc["args"]["bytes_out"] > 0

    # the simulated clock carries per-client round anatomy
    sim_tracks = {e["tid"]: e["args"]["name"] for e in obj["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"
                  and e["pid"] == obs_trace.PID_SIM}
    sim = [e for e in obj["traceEvents"]
           if e["pid"] == obs_trace.PID_SIM and e["ph"] == "X"]
    assert {e["name"] for e in sim} >= {"downlink", "compute", "uplink"}
    assert {sim_tracks[e["tid"]] for e in sim} == {"site-0", "site-1"}
    up = next(e for e in sim if e["name"] == "uplink")
    assert up["args"]["wire_bytes"] > 0
    # queue-depth counter samples ride the simulated clock too
    assert any(e["ph"] == "C" and e["name"] == "queue_depth"
               and e["pid"] == obs_trace.PID_SIM for e in obj["traceEvents"])

    # telemetry travels in the result: metrics snapshot + trace summary
    tele = result["telemetry"]
    json.dumps(tele)
    assert tele["traffic"]["messages"] > 0
    assert tele["trace"]["total_events"] > 0 and "runtime" in tele


# ---------------------------------------------------------------------------
# trace neutrality: tracing must not move events or flip weight bits
# ---------------------------------------------------------------------------

def _weight_bytes(weights):
    return {k: np.asarray(v).tobytes() for k, v in weights.items()}


@pytest.mark.slow
def test_tracing_is_neutral_sequential():
    from repro.fl.job import run_job

    base = run_job(_job_spec())
    traced = run_job(_job_spec(trace=True))
    assert _weight_bytes(base["final_weights"]) == \
        _weight_bytes(traced["final_weights"])
    assert base["wire_bytes"] == traced["wire_bytes"]
    assert base["messages"] == traced["messages"]


@pytest.mark.slow
def test_tracing_is_neutral_async():
    from repro.fl.job import build_job

    def run(trace):
        job = build_job(_job_spec(
            trace=trace,
            runtime={"policy": "sync", "dropout_prob": 0.2,
                     "network": {"kind": "hetero",
                                 "tiers": ["fiber", "lte", "3g"]}},
        ))
        result = job.run()
        timeline = [(e.time, e.seq, e.kind.value, e.client)
                    for e in job.sim.scheduler.timeline]
        return result, timeline

    base, tl_base = run(False)
    traced, tl_traced = run(True)
    # bitwise-identical weights AND an event-for-event identical timeline
    assert tl_base == tl_traced
    assert _weight_bytes(base["final_weights"]) == \
        _weight_bytes(traced["final_weights"])
    assert base["runtime_stats"] == traced["runtime_stats"]
    assert base["sim_time_s"] == traced["sim_time_s"]
