"""Quantized cross-pod collectives (mesh view of the paper's scheme):

int8-wire FedAvg must agree with fp32 pmean within blockwise-int8
round-off; bucketed (streaming) variant must agree exactly with the
unbucketed one.

Runs on 4 fake host devices (pod=2 x data=2) — set via conftest env for
this module only.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.core import collectives as C

mesh = jax.make_mesh((2, 2), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
n = 10_000
per_pod = jnp.asarray(rng.standard_normal((2, n)), jnp.float32)

def agg(x, kind):
    def f(x):
        x = x[0]  # local pod slice
        if kind == "fp32":
            out = jax.lax.pmean(x, "pod")
        elif kind == "int8":
            out = C.quantized_pod_mean(x, "pod")
        else:
            out = C.bucketed_quantized_pod_mean(x, bucket_bytes=4096 * 4, axis_name="pod")
        return out[None]
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                 out_specs=P("pod"), check_vma=False))(x)

exact = np.asarray(agg(per_pod, "fp32"))[0]
q = np.asarray(agg(per_pod, "int8"))[0]
qb = np.asarray(agg(per_pod, "bucket"))[0]
true = np.asarray(per_pod).mean(axis=0)

assert np.allclose(exact, true, atol=1e-6), "fp32 pmean mismatch"
# int8 wire: error bounded by mean of per-pod quantization steps
bound = float(np.abs(np.asarray(per_pod)).max()) / 127.0
assert np.max(np.abs(q - true)) <= bound, (np.max(np.abs(q - true)), bound)
assert np.allclose(q, qb, atol=1e-7), "bucketed != unbucketed"
print("OK")
"""


def test_quantized_pod_collectives_agree_with_fp32():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


FL_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
import sys
sys.argv = ["fl_train", "--arch", "qwen1.5-0.5b", "--smoke", "--rounds", "12",
            "--local-steps", "2", "--batch", "8", "--seq", "64",
            "--pods", "2", "--agg", "%s", "--lr", "3e-3"]
from repro.launch import fl_train
args = fl_train.main.__wrapped__ if hasattr(fl_train.main, "__wrapped__") else None
import argparse
ap = argparse.ArgumentParser()
for a in ("--arch",): pass
out = None
# call run() directly
ns = argparse.Namespace(arch="qwen1.5-0.5b", smoke=True, rounds=12, local_steps=2,
                        batch=8, seq=64, pods=2, lr=3e-3, alpha=0.5, agg="%s", seed=0)
out = fl_train.run(ns)
h = out["history"]
assert h[-1] < h[0] - 0.3, ("no convergence", h[0], h[-1])
print("OK", h[0], h[-1])
"""


@pytest.mark.slow
@pytest.mark.parametrize("agg", ["fp32", "int8"])
def test_mesh_fl_training_converges(agg):
    """Fig. 4/5 mesh-view analogue: federated loss decreases, int8 wire

    tracks fp32 (both must converge on the synthetic Markov corpus)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", FL_SCRIPT % (agg, agg)],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=560,
    )
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "OK" in out.stdout
