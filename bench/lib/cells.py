"""Find a cell's configuration, traffic mix, limits and metric readers by
name. Everything is data or a module of its own under ``bench/``:

- ``BENCHMARK.json`` (repo root): cells (``workloads``) and metrics;
- ``bench/configs/<config>.json``: a configuration's sizes, each of
  which reaches the program (``bench/lib/train.py::Program``);
- ``bench/mixes/<traffic>.json``: a traffic mix's parameters;
- ``bench/limits/<cell>.json``: the limits ``correct`` is held to;
- ``bench/metrics/<metric>.py``: a per-layer metric's reader, a module
  with ``read(run) -> float | None``;
- ``bench/reference/aip/<kind>.py``, ``bench/reference/policy/<kind>.py``:
  an AIP backbone or a policy network, as the configuration's
  ``aip.kind`` or ``policy.kind`` names it: its weights, its plain
  reference (a policy's learner with it) and its operation and word
  counts;
- ``bench/lib/policies/<kind>.py``: a policy network as the program
  trains and serves it;
- ``bench/lib/domains/<domain>.py`` and ``bench/reference/<domain>.py``:
  a domain's simulators as the program builds them, and its plain
  reference.

A new cell, configuration, mix, metric, AIP backbone or policy network
is a new file and a new entry; no existing file changes. So a
configuration with a new backbone brings its config, its mix, its
limits, any metric readers it needs, and ``bench/reference/aip/<kind>.py``;
one with a new policy network (one per agent region, say) brings
``bench/lib/policies/<kind>.py`` (``train_config``, ``program_init``,
``server``) and ``bench/reference/policy/<kind>.py`` (``init``,
``forward``, ``learn``, ``flops``, ``weight_words``). Set-up stops,
naming ``policy.kind``, where the two sides' parameters differ
(``weights.check_policy``).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    """-> the cell's ``workloads`` entry; KeyError if there is none."""
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _load_json(BENCH_DIR / "configs" / f"{name}.json")


def mix(name: str) -> dict:
    return _load_json(BENCH_DIR / "mixes" / f"{name}.json")


def limits(cell_name: str) -> dict:
    return _load_json(BENCH_DIR / "limits" / f"{cell_name}.json")


def metrics_of(cell_name: str, kind: str, root: Path = ROOT) -> list:
    """The metric entries a cell reports: ``kind`` is "end_to_end" or
    "per_layer". A metric without a ``workloads`` key is reported in every
    cell."""
    return [m for m in benchmark(root)[kind]
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric: str):
    """-> the ``read`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find(package: str, kind: str):
    """-> the module ``<package>.<kind>``: one file per kind, so a new kind
    is a new file. ValueError, naming the kind and the directory, where
    there is none."""
    name = f"{package}.{kind}"
    if kind.isidentifier():
        try:
            return importlib.import_module(name)
        except ModuleNotFoundError as e:
            if e.name != name:
                raise
    where = importlib.import_module(package).__path__[0]
    raise ValueError(f"unknown kind {kind!r}: no module {kind}.py in {where}")
