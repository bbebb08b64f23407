"""Each configuration's family has its own reference module
(``refmodels/<family>.py``): the leaves, loss, FLOP count and CPU cut
the harness uses come from it, a new architecture is a new file,
and the dense decoder's reference reads what it read before it moved
into ``refmodels/dense.py``."""
from __future__ import annotations

import hashlib
import os
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import harness  # noqa: E402
import refmodel  # noqa: E402
import tinycell  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
CONFIGS = [c["name"] for c in BENCH["configs"]]
WORKLOADS = [c["name"] for c in BENCH["workloads"]]

# Recorded on the CPU from refmodel.py as it stood before the dense
# decoder moved out of it into refmodels/dense.py: tinycell's sizes,
# seed 2**31 + 17. The digest is sha256 over each leaf's name and
# float32 bytes in leaf order (both cells' tiny weights are alike); the
# losses are the reference round's 20 local steps (2 clients x 10).
PIN_SEED = 2**31 + 17
PINNED_DIGEST = "5678b601a7b4887d3f72b0e2e8808b1978aa654119b8effc6baef3799c6499f4"
PINNED_LOSSES = {
    "qwen05b-b8-stream": [
        8.327173233032227, 8.347660064697266, 8.370290756225586, 8.362342834472656,
        8.336732864379883, 8.353113174438477, 8.32274341583252, 8.339454650878906,
        8.329551696777344, 8.341407775878906, 8.349629402160645, 8.363548278808594,
        8.383142471313477, 8.367591857910156, 8.344761848449707, 8.333255767822266,
        8.366649627685547, 8.334203720092773, 8.375596046447754, 8.336077690124512],
    "stablelm16b-nf4": [
        8.329476356506348, 8.3440580368042, 8.368389129638672, 8.36284065246582,
        8.33633041381836, 8.358959197998047, 8.317464828491211, 8.338274002075195,
        8.33094596862793, 8.340468406677246, 8.351442337036133, 8.363754272460938,
        8.38428783416748, 8.366353034973145, 8.34139347076416, 8.332886695861816,
        8.37061595916748, 8.329014778137207, 8.379188537597656, 8.333230018615723],
}

STUB = '''"""A stand-in architecture: one table of token weights."""
import jax.numpy as jnp


def leaf_specs(model):
    return {"table": ((model["vocab_size"], model["stub_width"]), 0.02)}


def loss_fn(params, tokens, model, dtype):
    return jnp.mean(jnp.square(params["table"].astype(dtype)[tokens]))


def step_flops(model, batch, seq):
    return 7.0 * model["stub_width"] * batch * seq


def tiny(model):
    return {"vocab_size": 16, "stub_width": 3}
'''


def _tree_digest(*paths: str) -> str:
    """sha256 over each file in ``paths`` and every file under each
    directory in them, but compiled bytecode."""
    files = []
    for top in paths:
        if os.path.isfile(top):
            files.append(top)
        for d, dirs, names in sorted(os.walk(top)):
            dirs[:] = sorted(x for x in dirs if x != "__pycache__")
            files += [os.path.join(d, f) for f in sorted(names)]
    h = hashlib.sha256()
    for path in files:
        h.update(os.path.relpath(path, ROOT).encode())
        with open(path, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()


@pytest.mark.parametrize("config", CONFIGS)
def test_configuration_names_a_reference_module(config):
    """Each configuration's ``family`` names a module that exports the
    four names."""
    model = cells.load_config(ROOT, BENCH, config)
    mod = refmodel.model_ref(model)
    assert all(callable(getattr(mod, name)) for name in refmodel.INTERFACE)
    specs = mod.leaf_specs(model)
    assert list(specs) == sorted(specs)
    assert set(mod.tiny(model)) <= set(model)


def test_unknown_reference_is_a_key_error():
    with pytest.raises(KeyError, match="known reference modules: .*'dense'"):
        refmodel.model_ref({"name": "x", "family": "no-such-architecture"})
    with pytest.raises(KeyError, match="'dense'"):
        refmodel.model_ref({"name": "x"})


def test_stub_reference_module_is_used_end_to_end(tmp_path, monkeypatch):
    """A module added as a new file and named by a configuration gives
    the harness its leaves, loss, FLOPs and CPU cut."""
    bench_files = (HERE, os.path.join(ROOT, "BENCHMARK.json"))
    before = _tree_digest(*bench_files)
    assert before != _tree_digest(HERE)
    (tmp_path / "stub.py").write_text(STUB)
    monkeypatch.setattr(refmodel, "REFMODELS", str(tmp_path))
    load_config = cells.load_config
    monkeypatch.setattr(cells, "load_config", lambda root, bench, name: {
        **load_config(root, bench, name), "family": "stub"})

    cell = tinycell.tiny_cell(WORKLOADS[0])
    assert (cell.config["vocab_size"], cell.config["stub_width"]) == (16, 3)

    w0 = harness.host_weights(cell.config, 2**31 + 3)
    assert {k: v.shape for k, v in w0.items()} == {"table": (16, 3)}

    ctx = harness._reader_context(cell, {}, [], types.SimpleNamespace(elems={}), None, 1.0)
    t = cell.traffic
    assert ctx.step_flops == 7.0 * 3 * t["batch"] * t["seq"]
    stub = refmodel.model_ref(cell.config)
    assert stub.__name__ == "chipbench_refmodel_stub"

    traffic = {**t, "local_steps": 1}
    batch_of = cell.batch_of(2**31 + 3)
    r = refmodel.reference_round(w0, cell.config, traffic, batch_of)
    down = {k: refmodel.codec_roundtrip(jnp.asarray(a), t["downlink"]) for k, a in w0.items()}
    want = stub.loss_fn(down, jnp.asarray(batch_of(0, 0)), cell.config, jnp.float32)
    assert r["step_losses"][0] == pytest.approx(float(want), rel=1e-6)
    assert set(r["weights"]) == {"table"}

    assert _tree_digest(*bench_files) == before


@pytest.mark.parametrize("workload", sorted(PINNED_LOSSES))
def test_pinned_cell_is_a_dense_workload(workload):
    """A pin cannot outlive its cell, or its cell's dense decoder."""
    cell = cells.find_cell(BENCH, workload)
    assert cells.load_config(ROOT, BENCH, cell["config"])["family"] == "dense"


@pytest.mark.parametrize("workload", sorted(PINNED_LOSSES))
def test_dense_reference_reads_the_pinned_round(workload):
    """Only the cells pinned: a cell added later has leaves and losses
    of its own."""
    cell = tinycell.tiny_cell(workload)
    w0 = harness.host_weights(cell.config, PIN_SEED)
    h = hashlib.sha256()
    for k, v in w0.items():
        h.update(k.encode())
        h.update(np.ascontiguousarray(v).tobytes())
    assert h.hexdigest() == PINNED_DIGEST
    r = refmodel.reference_round(w0, cell.config, cell.traffic, cell.batch_of(PIN_SEED))
    assert r["step_losses"] == pytest.approx(PINNED_LOSSES[workload], rel=1e-6)


def test_register_model_makes_tuples_of_lists():
    """JSON gives lists; ``ModelConfig``'s tuple fields must hash."""
    harness.import_program()
    from repro.configs.base import get_config

    model = cells.load_config(ROOT, BENCH, CONFIGS[0])
    arch = harness.register_model({**model, "arch_id": "chipbench.test.block_pattern",
                                   "block_pattern": ["attn", "attn"]})
    cfg = get_config(arch)
    assert cfg.block_pattern == ("attn", "attn")
    hash(cfg)
