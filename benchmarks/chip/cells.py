"""Everything the harness knows about a cell comes from files, by name.

* ``BENCHMARK.json`` (at the checkout root): the cells, each naming a
  configuration and a traffic mix, and the metrics.
* ``configs/<config>.json``: the model as it is run. Keys that are
  fields of the program's ``ModelConfig`` go to the program; the rest
  (init scales, norm epsilon, provenance) are read by the reference.
* ``traffic/<traffic>.json``: the federation's shape: clients, local
  steps, batch x seq, the codec on each hop, the aggregator, the
  optimizer settings the reference follows. :func:`job_spec` turns it
  into the program's job spec, :func:`token_rows` draws each client's
  token rows from the seed.
* ``limits/<workload>.json``: the numbers ``correct`` compares, each
  with its limit and the readings that set it.
* ``metrics/<metric>.py``: one reader per per-layer metric.
* ``refmodels/<reference>.py``: one reference module per architecture,
  named by the configuration (``refmodel.model_ref``).

Nothing here imports JAX or the program.
"""
from __future__ import annotations

import importlib.util
import json
import os
import types
from typing import Any, Callable

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _load_json(path: str) -> Any:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: str) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    known = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json (known: {known})")


def find_config(bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return cfg
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_config(root: str, bench: dict, name: str) -> dict:
    return _load_json(os.path.join(root, find_config(bench, name)["file"]))


def load_traffic(name: str) -> dict:
    return _load_json(os.path.join(HERE, "traffic", f"{name}.json"))


def load_limits(workload: str) -> dict:
    return _load_json(os.path.join(HERE, "limits", f"{workload}.json"))


def load_peaks(device_kind: str) -> dict:
    table = _load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def load_module(path: str, modname: str) -> types.ModuleType:
    """The Python file at ``path``, loaded as a module named ``modname``."""
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[[Any], Any]:
    """``metrics/<name>.py``'s ``read(ctx)``, loaded by file path."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    return load_module(path, f"chipbench_metric_{name}").read


def end_to_end_metrics(bench: dict, workload: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer_metrics(bench: dict, workload: str) -> list[dict]:
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])]


def model_fields(config: dict, field_names: set[str]) -> dict:
    """The configuration's keys that the program's ModelConfig takes."""
    return {k: v for k, v in config.items() if k in field_names}


def hop_stages(fmt: str | None) -> list[str]:
    return [f"quantize:{fmt}"] if fmt else []


def job_spec(config: dict, traffic: dict, seed: int) -> dict:
    """The program's declarative job spec for one cell."""
    return {
        "arch": config["arch_id"], "smoke": False, "seed": int(seed) % (1 << 31),
        "clients": traffic["clients"], "rounds": 1,
        "local_steps": traffic["local_steps"], "batch": traffic["batch"],
        "seq": traffic["seq"], "lr": traffic["optimizer"]["lr"],
        "partition": traffic["partition"], "transmission": traffic["transmission"],
        "driver": traffic["driver"], "chunk_mb": traffic["chunk_mb"],
        "server_streaming_agg": traffic["server_streaming_agg"],
        "pipeline": {"task_data": hop_stages(traffic["downlink"]),
                     "task_result": hop_stages(traffic["uplink"])},
        "aggregator": traffic["aggregator"],
    }


def token_rows(seed: int, client: int, key: int, batch: int, seq: int,
               vocab: int) -> np.ndarray:
    """One local step's token rows: uniform ids, a pure function of
    (seed, client, key), so every client and every step has rows of
    its own and the same seed gives the same rows."""
    rng = np.random.default_rng([int(seed) % (1 << 64), client, key])
    return rng.integers(0, vocab, (batch, seq), dtype=np.int32)


class TokenSource:
    """A client's data as the program's executor reads it:
    ``sample_at(batch, key)`` -> ``{"tokens", "labels"}``."""

    def __init__(self, seed: int, client: int, seq: int, vocab: int) -> None:
        self.seed, self.client, self.seq, self.vocab = seed, client, seq, vocab

    def sample_at(self, batch: int, key: int) -> dict[str, np.ndarray]:
        toks = token_rows(self.seed, self.client, key, batch, self.seq, self.vocab)
        return {"tokens": toks, "labels": toks.copy()}
