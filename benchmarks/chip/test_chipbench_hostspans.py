"""The readers of the host<->device and framing spans, on
hand-made spans and a hand-made idle-gap breakdown; the idle-gap
labelling under the doubled annotations a ``ProfiledTracer`` span makes
now that the program's own spans annotate too; and the program's
``dev.dispatch`` element counts against the benchmark's own
``KernelCounter`` on one tiny round of each cell."""
from __future__ import annotations

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import tinycell  # noqa: E402
import tracereduce as tr  # noqa: E402

WORKLOADS = [c["name"] for c in cells.load_benchmark(tinycell.ROOT)["workloads"]]


def _ev(name, ts, dur, tid=1, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": tid, "args": args}


def _ctx(**kw):
    base = dict(trace=None, spans=[], rounds=0, steps=0, step_flops=0.0,
                kernel_elems={}, peaks=None)
    return types.SimpleNamespace(**{**base, **kw})


def _read(name, ctx):
    return cells.metric_reader(name)(ctx)


# two rounds; times in microseconds
SPANS = [
    _ev("wire.transmit", 0, 1000),
    _ev("stream.item", 100, 400, nbytes=2_000_000, chunks=2),
    _ev("wire.decode_item", 150, 100),
    _ev("host.h2d", 160, 40, nbytes=1_000_000),
    _ev("dev.dispatch", 200, 20, kind="d8", elems=4096),
    _ev("agg.accept_item", 300, 50),
    _ev("host.d2h", 310, 30, nbytes=3_000_000),
    _ev("kernel.quantize_batch", 600, 300),
    _ev("host.pack", 610, 90, nbytes=4_000_000),
    _ev("host.h2d", 700, 60, nbytes=2_000_000),
    _ev("dev.sync", 760, 10),
    _ev("host.d2h", 770, 20, nbytes=1_000_000),
    _ev("stream.item", 100, 50, tid=2, nbytes=10, chunks=1),
]


def test_copy_readers():
    ctx = _ctx(spans=SPANS, rounds=2)
    assert _read("copy_ms", ctx) == pytest.approx((40 + 30 + 60 + 20) / 1e3 / 2)
    assert _read("h2d_gbps", ctx) == pytest.approx(3e6 / 100e-6 / 1e9)
    assert _read("d2h_gbps", ctx) == pytest.approx(4e6 / 50e-6 / 1e9)


def test_pack_and_frame_readers():
    ctx = _ctx(spans=SPANS, rounds=2)
    # thread 1: 400 us less the decode (150-250, holding the copy and
    # the dispatch) and the fold (300-350, holding a copy); the
    # enclosing wire.transmit is not a child. Thread 2: 50 us alone.
    assert _read("stream_frame_ms", ctx) == pytest.approx((400 - 100 - 50 + 50) / 1e3 / 2)


def test_idle_unattributed_share():
    gaps = [["kernel.quantize_batch", 4.0], ["host.d2h", 3.0], ["wire.transmit", 1.0],
            ["stream.item", 0.5], ["none", 0.25], ["host.pack", 0.25]]
    ctx = _ctx(trace={"window_s": 10.0, "busy_s": 1.0, "devices": 1, "idle_gaps": gaps})
    assert _read("idle_unattributed_share", ctx) == pytest.approx(100 * 5.25 / 9.0)
    ctx.trace["busy_s"] = 10.0
    assert _read("idle_unattributed_share", ctx) is None


@pytest.mark.parametrize("name", ["copy_ms", "h2d_gbps", "d2h_gbps", "stream_frame_ms",
                                  "idle_unattributed_share"])
def test_readers_need_their_spans(name):
    other = [_ev("round", 0, 100, round=1), _ev("wire.encode_item", 10, 20)]
    assert _read(name, _ctx(spans=other, rounds=1)) is None


def test_doubled_annotation_labels_as_single():
    """A benchmark span annotates once and the program's span inside it
    again under the same name, a little later and a little shorter."""
    single = [(0, 100, "round"), (10, 60, "host.d2h"), (20, 30, "dev.sync")]
    doubled = [(0, 100, "round"), (0.5, 99.5, "round"), (10, 60, "host.d2h"),
               (10.5, 59.5, "host.d2h"), (20, 30, "dev.sync"), (20.5, 29.5, "dev.sync")]
    one, two = tr.innermost_segments(single), tr.innermost_segments(doubled)
    s1, s2 = [t for t, _ in one], [t for t, _ in two]
    for t in (0.25, 5, 10.25, 15, 20.25, 25, 29.75, 45, 59.75, 80, 99.75, 100.5):
        assert tr.label_at(two, s2, t) == tr.label_at(one, s1, t), t


@pytest.mark.parametrize("workload", WORKLOADS)
def test_dispatch_elements_match_the_kernel_counter(workload):
    """Round 0 of the tiny cell, traced by a plain program ``Tracer``
    while the ``KernelCounter`` wraps the ops entry points."""
    import annotate
    import harness

    harness.import_program()
    from repro.obs import trace as obs_trace

    seed = 2**31 + 29
    cell = tinycell.tiny_cell(workload)
    job = harness.build(cell, seed)
    counter, tracer = annotate.KernelCounter(), obs_trace.Tracer()
    counter.install()
    try:
        with obs_trace.activate(tracer):
            harness.round0(job, cell, seed)
    finally:
        counter.uninstall()
    elems: dict[str, int] = {}
    for e in tracer.chrome_trace()["traceEvents"]:
        if e.get("name") == "dev.dispatch":
            elems[e["args"]["kind"]] = elems.get(e["args"]["kind"], 0) + e["args"]["elems"]
    assert tracer.dropped == 0
    assert elems and elems == counter.elems
