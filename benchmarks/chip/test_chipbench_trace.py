"""Trace and span reductions: on a small trace recorded on a TPU v5e
(``testdata/``, made by ``record_testtrace.py``) and on hand-made
spans."""
from __future__ import annotations

import glob
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import tracereduce as tr  # noqa: E402

TESTDATA = os.path.join(HERE, "testdata")
RECORDED = os.path.join(TESTDATA, "testtrace.json")


def test_union_length():
    total, merged = tr.union_length([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6
    assert merged == [[0, 3], [5, 8]]


def test_innermost_segments():
    spans = [(0, 10, "round"), (2, 5, "wire.encode_item"), (3, 4, "kernel.x")]
    seg = tr.innermost_segments(spans)
    starts = [t for t, _ in seg]
    label = lambda t: tr.label_at(seg, starts, t)  # noqa: E731
    assert [label(t) for t in (1, 2.5, 3.5, 4.5, 7, 11)] == [
        "round", "wire.encode_item", "kernel.x", "wire.encode_item", "round", "none"]


def _ev(name, ts, dur, tid=1, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": tid, "args": args}


def test_span_reductions():
    events = [
        _ev("round", 0, 100, round=0),
        _ev("round", 100, 100, round=1), _ev("round", 200, 100, round=2),
        _ev("wire.encode_item", 110, 40), _ev("kernel.quantize_batch", 120, 10),
        _ev("agg.accept_item", 140, 5), _ev("wire.encode_item", 210, 20),
        _ev("wire.encode_item", 210, 20, tid=2), _ev("kernel.x", 50, 10),
    ]
    inside, rounds = tr.window_events(events, first_round=1)
    assert rounds == 2
    assert all(e["ts"] >= 100 for e in inside)
    # 40 + 20 + 20 us of encode, less 10 + 5 nested on thread 1
    assert tr.self_time(inside, "wire.encode_item") == pytest.approx(65e-6)
    assert tr.span_totals(inside, ["agg.accept_item", "kernel.quantize_batch"]) == \
        pytest.approx(15e-6)


def _ctx(**kw):
    base = dict(trace=None, spans=[], rounds=0, steps=0, step_flops=0.0,
                kernel_elems={}, peaks=None)
    return types.SimpleNamespace(**{**base, **kw})


def test_readers_return_nothing_when_nothing_to_read():
    bench = cells.load_benchmark(os.path.dirname(os.path.dirname(HERE)))
    for m in bench["per_layer"]:
        assert cells.metric_reader(m["name"])(_ctx()) is None, m["name"]


def test_roofline_reader_by_hand():
    summary = {"window_s": 1.0, "busy_s": 0.25, "devices": 1,
               "module_s": {"jit__pallas_q8_full": 0.004, "jit__pallas_d8_full": 0.001,
                            "jit_local_step": 0.2},
               "module_calls": {"jit__pallas_q8_full": 2, "jit__pallas_d8_full": 1,
                                "jit_local_step": 4}}
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = _ctx(trace=summary, peaks=peaks, kernel_elems={"q8": 4096 * 1000},
               steps=4, step_flops=1e12)
    want = 100 * (5 * 4096 * 1000 + 4 * 1000) / 819e9 / 0.005
    assert cells.metric_reader("codec8_roofline")(ctx) == pytest.approx(want)
    assert cells.metric_reader("device_idle_share")(ctx) == pytest.approx(75.0)
    assert cells.metric_reader("local_step_mfu")(ctx) == pytest.approx(
        100 * 4e12 / (0.2 * 197e12))
    assert cells.metric_reader("round_mfu")(ctx) == pytest.approx(100 * 4e12 / 197e12)


def test_reduction_of_recorded_trace():
    """Two rounds, each a matmul program and a quantize stand-in under a
    ``wire.encode_item`` annotation with a 50 ms host sleep between."""
    meta = json.load(open(RECORDED))
    path = glob.glob(os.path.join(TESTDATA, "*.xplane.pb"))[0]
    s = tr.reduce_xplane(path, {"round", "wire.encode_item"})
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(sum(meta["rounds_s"]), rel=0.05)
    assert 0 < s["busy_s"] < s["window_s"]
    calls = s["module_calls"]
    assert calls["jit_local_step"] == 2 and calls["jit__pallas_q8_full"] == 2
    idle = dict(s["idle_gaps"])
    # the sleeps are idle time, charged to the span open during them
    assert idle["wire.encode_item"] >= 0.9 * sum(meta["sleeps_s"])
    assert s["device_ops"][0][0] == "jit_local_step"
