"""A cell that comes as new files and new ``BENCHMARK.json`` entries
alone is taken by the benchmark's own tests.

A copy of the benchmark gains a stand-in architecture (family
``guard``), its configuration, the cell's limits, the cell and a
per-layer metric that reads in that cell alone; no file of the copy is
edited. Over the copy, every test collects, no test that holds numbers
for particular cells picks the new cell up, and the tests that read
only a cell's files pass for it. The tests that run the program are
not run: the copy has no program, and the program no ``guard`` family.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from test_chipbench_refmodels import STUB  # noqa: E402

CELL, CONFIG, METRIC = "guard-cell", "guard-stub", "guard_reads_nothing"
# Tests that hold numbers for the cells they name.
PINNED = ("test_dense_reference_reads_the_pinned_round",)
# Tests that read only a cell's, a configuration's or a metric's files.
FILE_ONLY = (f"test_cell_found_by_name_from_files[{CELL}]",
             f"test_configuration_names_a_reference_module[{CONFIG}]",
             "test_readers_return_nothing_when_nothing_to_read")
TIMEOUT_S = 120


def _files(top: str) -> list[str]:
    """Every file under ``top`` but compiled bytecode, relative to it."""
    out = []
    for d, dirs, names in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        out += [os.path.relpath(os.path.join(d, f), top) for f in names]
    return sorted(out)


def _add_cell(root: str, bench: dict) -> None:
    """The new cell, as new files and entries appended to each list."""
    chip = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(chip, "refmodels", "guard.py"), "w") as fh:
        fh.write(STUB)
    with open(os.path.join(chip, "configs", f"{CONFIG}.json"), "w") as fh:
        json.dump({"name": CONFIG, "arch_id": f"chipbench.{CONFIG}", "family": "guard",
                   "vocab_size": 128, "stub_width": 8}, fh)
    shutil.copy(os.path.join(chip, "limits", f"{bench['workloads'][0]['name']}.json"),
                os.path.join(chip, "limits", f"{CELL}.json"))
    with open(os.path.join(chip, "metrics", f"{METRIC}.py"), "w") as fh:
        fh.write("def read(ctx):\n    return None\n")
    grown = copy.deepcopy(bench)
    grown["configs"].append({
        "name": CONFIG, "source": "a stand-in architecture: one table of token weights",
        "file": f"benchmarks/chip/configs/{CONFIG}.json", "reduced": [],
        "why": "a family that no configuration of the benchmark has"})
    grown["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "b8-stream", "chips": 1,
        "why": "a cell that comes as files only"})
    grown["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "guard", "moves": "round_s", "workloads": [CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(grown, fh, indent=1)


def _pytest(cwd: str, *args: str) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTEST_XDIST", "PYTEST_CURRENT"))}
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=TIMEOUT_S)


def test_cell_added_as_files_is_taken(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    root = str(tmp_path)
    chip = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    taken = _files(chip)
    _add_cell(root, bench)

    collected = _pytest(root, "--collect-only", "benchmarks/chip")
    assert collected.returncode == 0, collected.stdout[-4000:] + collected.stderr[-4000:]
    ids = [line for line in collected.stdout.splitlines() if "::" in line]
    assert not [i for i in ids if i.split("::")[1].startswith(PINNED) and CELL in i]
    run = [i for i in ids if i.split("::")[1] in FILE_ONLY]
    assert len(run) == len(FILE_ONLY), ids
    ran = _pytest(root, *run)
    assert ran.returncode == 0, ran.stdout[-4000:] + ran.stderr[-4000:]
    assert f"{len(run)} passed" in ran.stdout

    for rel in taken:
        with open(os.path.join(HERE, rel), "rb") as a, open(os.path.join(chip, rel), "rb") as b:
            assert a.read() == b.read(), rel
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        grown = json.load(fh)
    assert set(grown) == set(bench)
    for key, value in bench.items():
        kept = grown[key][:len(value)] if isinstance(value, list) else grown[key]
        assert kept == value, key
