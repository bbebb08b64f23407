#!/usr/bin/env python3
"""Record the small profiler trace that the reduction tests read.

    python3 benchmarks/chip/record_testtrace.py   # on the chip

Two ``round`` annotations, each holding a ``wire.encode_item``
annotation around a jitted ``local_step`` (a matmul) and a jitted
``_pallas_q8_full`` stand-in, with a host sleep between them so the
device idles under a known span. Writes the ``.xplane.pb`` under
``chiprun_out/chipbench/testtrace/`` and prints its path and the
host-clock lengths of the rounds and sleeps.
"""
from __future__ import annotations

import glob
import json
import os
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.getcwd(), "chiprun_out", "chipbench", "testtrace")


@jax.jit
def local_step(a):
    return a @ a


@jax.jit
def _pallas_q8_full(x):
    return jnp.round(x * 127.0).astype(jnp.int8)


def main() -> int:
    a = jnp.ones((2048, 2048), jnp.float32)
    local_step(a).block_until_ready()
    _pallas_q8_full(a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(OUT, profiler_options=opts)
    rounds, sleeps = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("round"):
            with jax.profiler.TraceAnnotation("wire.encode_item"):
                local_step(a).block_until_ready()
                s0 = time.perf_counter()
                time.sleep(0.05)
                sleeps.append(time.perf_counter() - s0)
                _pallas_q8_full(a).block_until_ready()
        rounds.append(time.perf_counter() - t0)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(OUT, "**", "*.xplane.pb"), recursive=True),
                  key=os.path.getmtime)[-1]
    print(json.dumps({"path": path, "bytes": os.path.getsize(path), "rounds_s": rounds,
                      "sleeps_s": sleeps, "kind": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
