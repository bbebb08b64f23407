"""``finish()`` of the FedAvg aggregators normalises the aggregate where
it already lives and makes no host copy of it beyond the one copy down:

* dense ``fedavg`` divides its running sums in place and hands them
  out, bitwise the parent formula ``(sum / W).astype(float32)``;
* ``quantized-fedavg`` scales its accumulators on the device in one
  donated dispatch, copies each down once and crops it as a view,
  bitwise the host formula ``(acc[:n] * (1 / W)).astype(float32)``;
* the read-only result feeds the next round's downlink.
"""
from __future__ import annotations

import tracemalloc

import numpy as np
import pytest

from repro.core.quantization import quantize
from repro.fl.aggregator import FedAvgAggregator, QuantizedFedAvgAggregator
from repro.kernels import ops

#: item name -> shape: several blocks with a ragged tail, one under a
#: block, a 0-d leaf
SHAPES = {"w": (600, 700), "b": (33,), "s": ()}
WEIGHTS = (3.0, 5.0, 2.0)


def _contributions(seed: int = 0) -> list[dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    return [{k: rng.standard_normal(s).astype(np.float32) for k, s in SHAPES.items()}
            for _ in WEIGHTS]


def _fold(agg, items) -> None:
    for contrib, w in zip(items, WEIGHTS):
        for name, value in contrib.items():
            agg.accept_item(name, value, w)
        agg.begin({"num_samples": w})


def _finish_peak(agg) -> tuple[dict[str, np.ndarray], int]:
    """``agg.finish()`` and the most host memory NumPy held during it."""
    tracemalloc.start()
    try:
        out = agg.finish()
        return out, tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()


def test_fedavg_finish_divides_the_running_sum_in_place():
    agg = FedAvgAggregator()
    _fold(agg, _contributions())
    sums = dict(agg._sum)
    expected = {k: (np.array(a) / float(sum(WEIGHTS))).astype(np.float32)
                for k, a in sums.items()}
    out, peak = _finish_peak(agg)
    assert out.keys() == expected.keys()
    for k, want in expected.items():
        got = out[k]
        assert got.dtype == np.float32 and got.shape == SHAPES[k]
        assert np.array_equal(got, want), k
        assert np.shares_memory(got, sums[k]), k
    # the aggregator keeps no reference to what it handed out
    assert agg._sum == {}
    assert not any(np.shares_memory(s, a) for s in agg._scratch.values()
                   for a in out.values())
    assert peak < sum(a.nbytes for a in out.values()) // 4


def test_fedavg_folds_a_0d_leaf_across_contributions():
    agg = FedAvgAggregator()
    items = _contributions(1)
    _fold(agg, items)
    want = sum(np.float32(c["s"]) * np.float32(w) for c, w in zip(items, WEIGHTS))
    got = agg.finish()["s"]
    assert isinstance(got, np.ndarray) and got.shape == ()
    assert np.isclose(got, want / np.float32(sum(WEIGHTS)), rtol=1e-6)


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_quantized_finish_scales_on_the_device(backend):
    with ops.backend(backend):
        agg = QuantizedFedAvgAggregator()
        items = [{k: quantize(v, "blockwise8") for k, v in c.items()}
                 for c in _contributions(2)]
        _fold(agg, items)
        accs = dict(agg._acc)
        inv = np.float32(1.0) / np.float32(sum(WEIGHTS))
        expected = {}
        for k, acc in accs.items():
            shape = SHAPES[k]
            n = int(np.prod(shape))
            host = np.array(acc)
            expected[k] = (host.reshape(-1)[:n].reshape(shape) * inv).astype(np.float32)
        out, peak = _finish_peak(agg)
    assert out.keys() == expected.keys()
    for k, want in expected.items():
        got = out[k]
        assert got.dtype == np.float32 and got.shape == SHAPES[k]
        assert np.array_equal(got, want), k
        # the crop is a view of the one copy down, which JAX made read-only
        assert not got.flags.writeable, k
    assert all(a.is_deleted() for a in accs.values())  # donated to the scale
    assert agg._acc == {}
    assert peak <= sum(a.nbytes for a in out.values())


def test_scale_into_is_one_donated_program_over_the_tuple():
    import jax.numpy as jnp

    accs = (jnp.arange(8, dtype=jnp.float32).reshape(2, 4), jnp.ones((3,), jnp.float32))
    want = [np.asarray(a) * np.float32(0.25) for a in accs]
    got = ops.scale_into(accs, np.float32(0.25))
    assert all(a.is_deleted() for a in accs)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and np.array_equal(np.asarray(g), w)


def test_quantized_fedavg_read_only_result_feeds_the_next_downlink():
    """Two rounds: round 1's downlink encodes round 0's read-only
    aggregate, and streaming and batch folds agree bitwise."""
    from repro.fl.job import run_job

    def run(streaming: bool) -> dict[str, np.ndarray]:
        out = run_job({"arch": "qwen1.5-0.5b", "smoke": True, "seed": 3, "rounds": 2,
                       "local_steps": 1, "clients": 2, "batch": 2, "seq": 16,
                       "server_streaming_agg": streaming,
                       "aggregator": "quantized-fedavg",
                       "pipeline": {"task_data": ["quantize:blockwise8"],
                                    "task_result": ["quantize:blockwise8"]}})
        return out["final_weights"]

    stream, batch = run(True), run(False)
    assert stream.keys() == batch.keys()
    assert any(not np.asarray(v).flags.writeable for v in stream.values())
    for k in stream:
        a, b = np.asarray(stream[k]), np.asarray(batch[k])
        assert a.dtype == np.float32 and np.array_equal(a, b), k
