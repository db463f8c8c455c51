"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (one entry of ``workloads``) is found by name. Its configuration
file, its traffic mix (``traffic/<traffic>.json``), its correctness limits
(``limits/<cell>.json``) and the reader of each per-layer metric
(``metrics/<metric>.py``) are all found by the names in ``BENCHMARK.json``,
so a later cell, mix or metric is a new file and a new entry, never an
edit here.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict  # the configuration file as run
    traffic: dict  # the traffic mix
    limits: dict  # number compared -> its limit
    end_to_end: tuple  # BENCHMARK.json end_to_end entries this cell reports
    per_layer: tuple  # BENCHMARK.json per_layer entries this cell reports


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reported_in(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", (cell,))


def load_cell(name: str, config_file: str, traffic: str, chips: int,
              end_to_end=(), per_layer=(), root: str = ROOT) -> Cell:
    """A cell from its files: the configuration, ``traffic/<traffic>.json``
    and ``limits/<name>.json``."""
    limits = _read_json(os.path.join(HERE, "limits", name + ".json"))
    return Cell(
        name=name,
        chips=chips,
        config=_read_json(os.path.join(root, config_file)),
        traffic=_read_json(os.path.join(HERE, "traffic", traffic + ".json")),
        limits={k: float(v["limit"]) for k, v in limits["limits"].items()},
        end_to_end=tuple(end_to_end),
        per_layer=tuple(per_layer),
    )


def resolve(bench: dict, name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(by_name)}")
    work = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return load_cell(
        name, configs[work["config"]]["file"], work["traffic"],
        int(work["chips"]),
        end_to_end=(m for m in bench["end_to_end"] if _reported_in(m, name)),
        per_layer=(m for m in bench["per_layer"] if _reported_in(m, name)),
        root=root,
    )


def load_reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
