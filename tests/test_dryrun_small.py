"""Launch-layer integration: the dry-run machinery (build_step, sharding

rules, input specs, roofline analysis) must lower+compile every step kind
on a small fake-device mesh — the same code path the 512-chip production
dry-run uses, kept CI-sized via subprocess-scoped XLA device faking.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.launch.dryrun import build_step
from repro.launch import sharding as SH
from repro.launch.specs import plan_for, apply_variant
import repro.launch.specs as SP
from repro.launch import roofline as RL
from repro.models import layers as ML
from repro.utils import hlo as H

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
# shrink shapes for CI
for name, (S, B) in {"train_4k": (128, 8), "prefill_32k": (256, 4),
                     "decode_32k": (256, 8), "long_500k": (512, 2)}.items():
    SP.INPUT_SHAPES[name] = dict(SP.INPUT_SHAPES[name], seq_len=S, global_batch=B)

out = {}
for arch in ("granite-8b", "dbrx-132b", "xlstm-125m", "recurrentgemma-2b", "whisper-small"):
    cfg = get_smoke_config(arch).with_overrides(param_dtype=jnp.bfloat16, activ_dtype=jnp.bfloat16)
    for shape in ("train_4k", "decode_32k", "long_500k"):
        plan = plan_for(cfg, shape)
        c2 = apply_variant(cfg, plan)
        ML.set_sharding_context(mesh, SH.DEFAULT_RULES)
        step, args, in_sh, out_sh, donate = build_step(c2, plan, mesh)
        with mesh:
            compiled = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                               donate_argnums=donate or ()).lower(*args).compile()
        ML.set_sharding_context(None, None)
        txt = compiled.as_text()
        m = H.analyze_module(txt)
        assert m["flops"] > 0, (arch, shape)
        assert m["traffic_bytes"] > 0, (arch, shape)
        info = SP.INPUT_SHAPES[shape]
        rep = RL.analyze(arch=arch, shape=shape, mesh_name="2x4", variant=plan.variant,
                         chips=8, cfg=c2, kind=plan.kind, seq_len=info["seq_len"],
                         global_batch=info["global_batch"], cost={}, hlo_text=txt)
        assert rep.bottleneck in ("compute", "memory", "collective")
        out[f"{arch}/{shape}"] = rep.bottleneck
print(json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_lowers_all_step_kinds_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        cwd=ROOT,
        env=env,
        timeout=560,
    )
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out) == 15  # 5 archs x 3 shapes
