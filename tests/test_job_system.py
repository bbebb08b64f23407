"""Declarative job system: every example job spec runs end-to-end and the

configuration knobs (quantization fmt, EF, DP, fused server aggregation,
transmission) actually take effect.
"""
import glob
import os

import numpy as np
import pytest

from repro.fl.job import run_job, run_job_file

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = {
    "arch": "llama3.2-1b",
    "smoke": True,
    "rounds": 3,
    "local_steps": 2,
    "clients": 2,
    "batch": 4,
    "seq": 32,
}


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(ROOT, "examples", "jobs", "*.json"))))
def test_example_jobs_run(path):
    out = run_job_file(path)
    assert out["messages"] > 0 and out["wire_bytes"] > 0
    assert len(out["history"]) > 0
    assert np.isfinite(out["history"][-1])


def test_quantization_config_changes_wire_bytes():
    a = run_job({**BASE, "quantization": None})
    b = run_job({**BASE, "quantization": {"fmt": "nf4"}})
    assert b["wire_bytes"] < a["wire_bytes"] / 5.0  # ~7x smaller wire


def test_fused_server_aggregation_matches_plain():
    plain = run_job({**BASE, "quantization": {"fmt": "blockwise8"}, "seed": 3})
    fused = run_job(
        {**BASE, "quantization": {"fmt": "blockwise8"},
         "server_quantized_aggregation": True, "seed": 3}
    )
    for k in plain["final_weights"]:
        np.testing.assert_allclose(
            np.asarray(plain["final_weights"][k], np.float32),
            np.asarray(fused["final_weights"][k], np.float32),
            rtol=2e-4,
            atol=2e-5,
        )


def test_job_runtime_sync_matches_sequential():
    """Round-trip of the "runtime" spec keys: the sync policy through the
    declarative surface is bitwise-equal to the plain sequential job."""
    seq = run_job(dict(BASE))
    sync = run_job({**BASE, "runtime": {"policy": "sync"}})
    assert sync["policy"] == "sync" and sync["sim_time_s"] > 0
    for k in seq["final_weights"]:
        np.testing.assert_array_equal(
            np.asarray(seq["final_weights"][k]), np.asarray(sync["final_weights"][k])
        )


def test_job_runtime_matches_direct_construction():
    """run_job(spec) is exactly build_job(spec).run(): the declarative
    surface adds nothing over direct FLSimulator construction."""
    from repro.fl.job import build_job
    from repro.fl.simulator import FLSimulator
    from repro.runtime import FedAsyncPolicy

    spec = {**BASE, "runtime": {"policy": "fedasync", "total_tasks": 6,
                                "network": {"kind": "hetero", "tiers": ["fiber", "3g"]}}}
    via_run = run_job(spec)
    job = build_job(spec)
    assert isinstance(job.sim, FLSimulator)
    assert isinstance(job.sim.scheduler.policy, FedAsyncPolicy)
    direct = job.run()
    for k in via_run["final_weights"]:
        np.testing.assert_array_equal(
            np.asarray(via_run["final_weights"][k]), np.asarray(direct["final_weights"][k])
        )
    assert via_run["runtime_stats"] == direct["runtime_stats"]


def test_job_runtime_fedasync_completes_multi_round():
    out = run_job({**BASE, "runtime": {"policy": "fedasync", "total_tasks": 8,
                                       "mixing_rate": 0.5,
                                       "network": {"kind": "hetero"}}})
    assert out["policy"] == "fedasync"
    assert out["runtime_stats"]["model_updates"] == 8
    assert out["sim_time_s"] > 0 and np.isfinite(out["history"][-1])


def test_job_runtime_tiered_completes_multi_round():
    out = run_job({**BASE, "rounds": 4,
                   "runtime": {"policy": "tiered", "num_tiers": 2,
                               "network": {"kind": "hetero", "tiers": ["fiber", "3g"]}}})
    assert out["policy"] == "tiered"
    assert out["runtime_stats"]["model_updates"] == 4  # one per round barrier
    assert np.isfinite(out["history"][-1])


def test_job_runtime_availability_and_adaptive_quantization():
    out = run_job({**BASE,
                   "quantization": {"fmt": "adaptive", "budget_s": 1.0},
                   "runtime": {"policy": "fedbuff", "buffer_size": 2, "total_tasks": 6,
                               "network": {"profiles": {"site-0": "fiber", "site-1": "3g"},
                                           "compute_base_s": 0.5},
                               "availability": {"kind": "random", "mean_online_s": 60,
                                                "mean_offline_s": 20, "horizon_s": 300,
                                                "seed": 1}}})
    assert out["policy"] == "fedbuff"
    fmts = out["adaptive_fmts"]
    assert fmts["site-0"] != fmts["site-1"]  # precision tracked the link
    assert out["runtime_stats"]["completions"] == 6


def test_job_runtime_rejects_unknown_policy():
    with pytest.raises(ValueError, match="unknown runtime policy"):
        run_job({**BASE, "runtime": {"policy": "carrier-pigeon"}})


def test_job_runtime_rejects_quantized_aggregation_with_async_policies():
    # fedbuff/fedasync bypass the aggregator and skip quantized payload
    # items, so this combination would silently train nothing
    with pytest.raises(ValueError, match="server_quantized_aggregation"):
        run_job({**BASE, "quantization": {"fmt": "blockwise8"},
                 "server_quantized_aggregation": True,
                 "runtime": {"policy": "fedasync", "total_tasks": 4}})


def test_job_rejects_quantized_aggregation_with_adaptive_precision():
    # clients on different links ship different formats; the fused
    # aggregator needs one uniform wire format
    with pytest.raises(ValueError, match="mixed formats"):
        run_job({**BASE, "quantization": {"fmt": "adaptive"},
                 "server_quantized_aggregation": True})


def test_dp_sigma_changes_result():
    a = run_job({**BASE, "seed": 1})
    b = run_job({**BASE, "dp_sigma": 0.01, "seed": 1})
    diffs = [
        float(np.max(np.abs(np.asarray(a["final_weights"][k], np.float32)
                            - np.asarray(b["final_weights"][k], np.float32))))
        for k in a["final_weights"]
    ]
    assert max(diffs) > 1e-4  # noise visibly applied


def test_compile_cache_is_placed_from_outside_or_fixed_in_checkout(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code;
    unset, the cache goes to one fixed directory inside the checkout."""
    import jax

    from repro.utils import jax_env

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(jax_env.CACHE_ENV, "/placed/by/the/host")
        assert jax_env.enable_compile_cache() == "/placed/by/the/host"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(jax_env.CACHE_ENV)
        path = jax_env.enable_compile_cache()
        assert path == str(jax_env.DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert (jax_env.DEFAULT_CACHE_DIR.parent / "src" / "repro" / "fl" / "job.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
