"""The benchmark's files, counts and result schema (CPU, no chip)."""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import flopcount  # noqa: E402
import refmodel  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
WORKLOADS = [c["name"] for c in BENCH["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_found_by_name_from_files(workload):
    cell = cells.find_cell(BENCH, workload)
    config = cells.load_config(ROOT, BENCH, cell["config"])
    traffic = cells.load_traffic(cell["traffic"])
    limits = cells.load_limits(workload)
    assert config["name"] == cell["config"]
    assert {"clients", "local_steps", "batch", "seq", "optimizer"} <= set(traffic)
    assert limits["numbers"] and all("limit" in v for v in limits["numbers"].values())
    assert cells.end_to_end_metrics(BENCH, workload)
    per_layer = cells.per_layer_metrics(BENCH, workload)
    assert per_layer
    for m in per_layer:
        assert callable(cells.metric_reader(m["name"]))


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        cells.find_cell(BENCH, "no-such-cell")
    with pytest.raises(KeyError):
        cells.find_config(BENCH, "no-such-config")
    with pytest.raises(KeyError):
        cells.load_peaks("TPU v0 imaginary")


def test_peaks_table():
    peaks = cells.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["hbm_bytes"] == 16e9


@pytest.mark.parametrize("config,params", [
    ("qwen1.5-0.5b", 619_570_176),
    ("stablelm-2-1.6b-d4", 616_605_696),
])
def test_parameter_counts(config, params):
    model = cells.load_config(ROOT, BENCH, config)
    specs = refmodel.model_ref(model).leaf_specs(model)
    assert (sum(math.prod(shape) for shape, _ in specs.values()), len(specs)) == (params, 15)


def test_step_flops_by_hand():
    model = cells.load_config(ROOT, BENCH, "qwen1.5-0.5b")
    # 24 x (4 x 1024^2 + 3 x 1024 x 2816) + 1024 x 151936
    dense = refmodel.model_ref(model)
    assert dense.matmul_params(model) == 463_863_808
    per_token = 6 * 463_863_808 + 12 * 24 * 512 * 1024
    assert dense.step_flops(model, 4, 512) == per_token * 2048


@pytest.mark.parametrize("kind,elems,nbytes", [
    ("q8", 4096 * 3, 5 * 4096 * 3 + 4 * 3),
    ("d8", 4097, 5 * 4097 + 4 * 2),
    ("q4", 64 * 10, 4.5 * 640 + 4 * 10),
    ("d4", 65, 4.5 * 65 + 4 * 2),
    ("fold8", 4096, 9 * 4096 + 4),
])
def test_codec_bytes_by_hand(kind, elems, nbytes):
    assert flopcount.codec_bytes(kind, elems) == nbytes


def test_token_rows_differ_and_repeat():
    a = cells.token_rows(2**31 + 5, 0, 0, 4, 16, 100)
    assert a.dtype.name == "int32" and a.shape == (4, 16)
    assert (a == cells.token_rows(2**31 + 5, 0, 0, 4, 16, 100)).all()
    others = [cells.token_rows(2**31 + 5, 1, 0, 4, 16, 100),
              cells.token_rows(2**31 + 5, 0, 1, 4, 16, 100),
              cells.token_rows(2**31 + 6, 0, 0, 4, 16, 100)]
    assert all((a != b).any() for b in others)


def test_command_refuses_without_a_chip(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_command_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, BENCH["command"][1], "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_result_line_schema(tmp_path, monkeypatch):
    import harness
    import tinycell

    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    for trace in (False, True):
        res = tinycell.run_tiny(WORKLOADS[0], seed=2**31 + 11, trace=trace)
        assert list(res)[-1] == "checks"
        assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
        assert isinstance(res["correct"], bool) and res["correct"]
        assert res["attempted"] >= 2 and res["failed"] == 0
        dev = res["device"]
        assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
        for name, c in res["checks"].items():
            assert set(c) == {"value", "limit"}
        for m in res["metrics"].values():
            assert set(m) == {"value", "unit"} and math.isfinite(m["value"])
        if trace:
            assert {"busy_s", "window_s"} <= set(dev)
            assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
            assert all(len(v) <= 10 for v in res["breakdown"].values())
        else:
            assert {"round_s", "setup_s", "host_peak_gb"} <= set(res["metrics"])
        json.dumps(res)
