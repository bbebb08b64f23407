"""Host<->device spans of a traced federation round, on the two wire
paths the chip benchmark's cells run (blockwise8 both hops with the
device int8 fold; nf4 both hops with the host dense fold), at smoke
size on the CPU, through nothing but the program's own job surface.

* every span the round's host<->device boundaries open appears inside
  the round;
* ``dev.dispatch`` counts, per kind, the elements the code path implies
  for the model's parameters;
* the bytes of ``host.d2h`` and ``host.h2d`` per round are the ones the
  code path implies for the model's parameters;
* tracing stays observational: traced and untraced runs give
  bitwise-equal weights;
* a ``jax.profiler`` trace of a traced run holds the program's spans as
  host events.
"""
from __future__ import annotations

import glob
import math
import os

import numpy as np
import pytest

SPANS = ("dev.dispatch", "dev.join", "host.h2d", "dev.sync", "host.d2h",
         "host.fold", "stream.item", "client.train")
BLOCK8, BLOCK4 = 4096, 64

#: the wire paths of the chip benchmark's cells, by cell name, on
#: smoke-size models of the cells' architectures
PATHS = {
    "qwen05b-b8-stream": {"arch": "qwen1.5-0.5b", "fmt": "blockwise8",
                          "aggregator": "quantized-fedavg"},
    "stablelm16b-nf4": {"arch": "stablelm-1.6b", "fmt": "nf4",
                        "aggregator": "fedavg"},
}


def _opened(path: str) -> set[str]:
    """The spans of ``SPANS`` a round of the path opens: blockwise8
    folds and normalises on the device, so only nf4 does NumPy
    arithmetic (``host.fold``)."""
    return set(SPANS) - ({"host.fold"} if path == "qwen05b-b8-stream" else set())


def _spec(path: str, trace: bool) -> dict:
    p = PATHS[path]
    return {"arch": p["arch"], "smoke": True, "seed": 7, "rounds": 2,
            "local_steps": 1, "clients": 2, "batch": 2, "seq": 32,
            "transmission": "container", "driver": "loopback", "chunk_mb": 0.25,
            "server_streaming_agg": True, "aggregator": p["aggregator"],
            "pipeline": {"task_data": [f"quantize:{p['fmt']}"],
                         "task_result": [f"quantize:{p['fmt']}"]},
            "trace": trace}


def _run(path: str, trace: bool) -> dict:
    from repro.fl.job import build_job

    job = build_job(_spec(path, trace))
    out = job.run()
    run = {"weights": {k: np.array(v) for k, v in out["final_weights"].items()}}
    if trace:
        events = job.sim.tracer.chrome_trace()["traceEvents"]
        last = max((e for e in events if e.get("ph") == "X" and e["name"] == "round"),
                   key=lambda e: e["args"]["round"])
        lo, hi = last["ts"], last["ts"] + last["dur"]
        run["spans"] = [e for e in events if e.get("ph") == "X"
                        and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]
        run["dropped"] = job.sim.tracer.dropped
    return run


@pytest.fixture(scope="module", params=sorted(PATHS))
def runs(request):
    return request.param, _run(request.param, True), _run(request.param, False)


def _sums(spans: list[dict], name: str, key: str) -> int:
    return sum(e["args"][key] for e in spans if e["name"] == name)


def _layout(path: str, weights: dict) -> tuple[int, int, int, int]:
    """(parameters, block, blocks, code bytes a block) of the model."""
    sizes = [int(np.prod(w.shape)) for w in weights.values()]
    block, code = (BLOCK8, BLOCK8) if path == "qwen05b-b8-stream" else (BLOCK4, BLOCK4 // 2)
    return sum(sizes), block, sum(math.ceil(n / block) for n in sizes), code


def _expected_bytes(path: str, weights: dict) -> tuple[int, int]:
    """(host.h2d, host.d2h) bytes of one round of two clients, from the
    code path. Every float leaf is quantized; a format group is joined
    at whole blocks on the device, slice by slice, from the tensors
    where they are: a downlink's NumPy leaves go up as they are, an
    uplink's trained weights are already on the device. Each message's
    codes and absmaxes (4 bytes a block) come to the host once.
    blockwise8: the downlink decodes from NumPy payloads, the clients
    train on the decoded device arrays, the device folds each uplink
    item from its NumPy payload, and ``finish`` copies the accumulators
    back. nf4: each uplink item is decoded on the device and copied
    back for the host fold."""
    params, block, blocks, code = _layout(path, weights)
    joined, codes = 4 * block * blocks, (code + 4) * blocks
    # up: 2 downlinks' leaves; 2 downlink decodes' and 2 folds' (b8) or
    # 4 decodes' (nf4) codes. Down: 4 encodes' codes, then b8's
    # accumulators or nf4's 2 decoded uplinks
    if path == "qwen05b-b8-stream":
        return 2 * 4 * params + 4 * codes, 4 * codes + joined
    return 2 * 4 * params + 4 * codes, 4 * codes + 2 * 4 * params


def _expected_elems(path: str, weights: dict) -> dict[str, int]:
    """Elements per kind of one round: 4 encodes over the joined
    blocks; blockwise8 decodes the downlink at each tensor's size and
    folds each uplink over whole blocks, nf4 decodes both hops at each
    tensor's size."""
    params, block, blocks, _ = _layout(path, weights)
    if path == "qwen05b-b8-stream":
        return {"q8": 4 * block * blocks, "d8": 2 * params, "fold8": 2 * block * blocks}
    return {"q4": 4 * block * blocks, "d4": 4 * params}


def test_round_spans_inside_the_window(runs):
    path, traced, _ = runs
    assert traced["dropped"] == 0
    names = {e["name"] for e in traced["spans"]}
    want = _opened(path)
    assert want <= names, want - names
    assert not {n for n in names if n.startswith(("kernel.", "agg."))} - {
        "kernel.quantize_batch", "kernel.dequantize_batch",
        "kernel.dequant_accumulate8", "agg.begin", "agg.accept_item", "agg.finish"}


def test_finish_scales_on_the_device_path(runs):
    """blockwise8's ``finish`` scales every accumulator (whole blocks)
    in one ``dev.scale`` dispatch and copies each sum down once: it
    opens no ``host.fold``. Only nf4's dense fold does NumPy arithmetic
    there, and it dispatches no scale."""
    path, traced, _ = runs
    spans = traced["spans"]
    (finish,) = [e for e in spans if e["name"] == "agg.finish"]

    def inside(name):
        return [e for e in spans if e["name"] == name and e["tid"] == finish["tid"]
                and finish["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= finish["ts"] + finish["dur"]]

    scales = [e["args"]["elems"] for e in spans if e["name"] == "dev.scale"]
    if path == "qwen05b-b8-stream":
        _, block, blocks, _ = _layout(path, traced["weights"])
        assert not inside("host.fold")
        assert scales == [block * blocks] and len(inside("dev.scale")) == 1
    else:
        assert inside("host.fold") and not scales


def test_dispatch_elements_follow_the_parameters(runs):
    path, traced, _ = runs
    elems: dict[str, int] = {}
    for e in traced["spans"]:
        if e["name"] == "dev.dispatch":
            elems[e["args"]["kind"]] = elems.get(e["args"]["kind"], 0) + e["args"]["elems"]
    assert elems == _expected_elems(path, traced["weights"])


def test_copy_bytes_follow_the_parameters(runs):
    path, traced, _ = runs
    h2d, d2h = _expected_bytes(path, traced["weights"])
    assert _sums(traced["spans"], "host.h2d", "nbytes") == h2d
    assert _sums(traced["spans"], "host.d2h", "nbytes") == d2h


def test_h2d_rides_inside_its_dispatch(runs):
    """Every upload is inside the dispatch that carries it, or inside
    the on-device join of the slice it feeds."""
    _path, traced, _ = runs
    by_tid: dict = {}
    for e in traced["spans"]:
        by_tid.setdefault(e["tid"], []).append(e)
    for e in traced["spans"]:
        if e["name"] != "host.h2d":
            continue
        assert any(d["name"] in ("dev.dispatch", "dev.join") and d["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= d["ts"] + d["dur"]
                   for d in by_tid[e["tid"]]), e


def test_tracing_is_observational(runs):
    _path, traced, untraced = runs
    a, b = traced["weights"], untraced["weights"]
    assert a.keys() == b.keys()
    for k in a:
        assert a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]), k


def test_profiler_trace_holds_the_program_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from repro.fl.job import run_job

    spec = {**_spec("qwen05b-b8-stream", True), "rounds": 1}
    with jax.profiler.trace(str(tmp_path)):
        run_job(spec)
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[0]
    host = {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert {"round", "wire.transmit", "kernel.quantize_batch",
            *_opened("qwen05b-b8-stream")} <= host
