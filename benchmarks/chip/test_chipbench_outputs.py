"""``correct`` at a size a CPU test run can hold: a sound run passes;
the control and each fault planted under the timed path fail, against
each cell's own limits."""
from __future__ import annotations

import os
import sys

import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import harness  # noqa: E402
import outcheck  # noqa: E402
import tinycell  # noqa: E402

CELLS = cells.load_benchmark(tinycell.ROOT)["workloads"]
WORKLOADS = [c["name"] for c in CELLS]


def _unchanged_state(monkeypatch):
    """The local step returns its state unchanged."""
    from repro.fl import job as job_module

    monkeypatch.setattr(job_module, "adamw_update",
                        lambda params, grads, state, lr, **_kw: (params, state, {}))


def _half_batch(monkeypatch):
    """Every local step sees half its rows; the loss is their mean."""
    from repro.fl import job as job_module

    orig = job_module._jit_local_step

    def half(model, lr):
        step = orig(model, lr)
        return lambda p, opt, batch: step(
            p, opt, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

    monkeypatch.setattr(job_module, "_jit_local_step", half)


def _uplink_dropped(monkeypatch):
    """The second client's uplink is never folded."""
    orig = harness.build

    def build(cell, seed):
        job = orig(cell, seed)
        agg = job.sim.controller.aggregator
        begin, accept = agg.begin, agg.accept_item
        skip = {"on": False}

        def drop_begin(meta):
            skip["on"] = meta.get("client") == "site-1"
            return 1.0 if skip["on"] else begin(meta)

        def drop_accept(name, value, weight):
            if not skip["on"]:
                accept(name, value, weight)

        agg.begin, agg.accept_item = drop_begin, drop_accept
        return job

    monkeypatch.setattr(harness, "build", build)


def _codec_bypassed(monkeypatch):
    """Both hops go unquantized (and the fold takes float32 items)."""
    orig = cells.job_spec

    def job_spec(config, traffic, seed):
        spec = orig(config, traffic, seed)
        spec["pipeline"] = {"task_data": [], "task_result": []}
        spec["aggregator"] = "fedavg"
        return spec

    monkeypatch.setattr(cells, "job_spec", job_spec)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "uplink_dropped": _uplink_dropped, "codec_bypassed": _codec_bypassed}


def _can_have(fault, traffic):
    """A fault that the cell's traffic cannot have leaves its round as
    it was: no second uplink to drop, no codec on either hop to bypass."""
    return {"uplink_dropped": traffic["clients"] >= 2,
            "codec_bypassed": bool(traffic["downlink"] or traffic["uplink"])}.get(fault, True)


CELL_FAULTS = [pytest.param(cell["name"], fault, id=f"{cell['name']}-{fault}")
               for cell in CELLS
               for fault in sorted(FAULTS)
               if _can_have(fault, cells.load_traffic(cell["traffic"]))]


@pytest.fixture(autouse=True)
def _trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT", str(tmp_path))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    res = tinycell.run_tiny(workload, seed=5, seconds=0.05)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("workload,fault", CELL_FAULTS)
def test_planted_fault_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = tinycell.run_tiny(workload, seed=6, seconds=0.05)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(workload):
    """The reference computed in bfloat16, in the program's place. Its
    loss is a bfloat16 scalar near ln(vocab), so its gap from the
    float32 loss varies from seed to seed; on these three it is wide."""
    harness.import_program()
    cell = tinycell.tiny_cell(workload)
    for seed in (10, 11, 12):
        w0 = harness.host_weights(cell.config, seed)
        numbers, _ = harness.reference_numbers(cell, seed, None, w0, dtype=jnp.bfloat16)
        ok, checks = outcheck.verdict(numbers, cell.limits)
        assert not ok, checks
