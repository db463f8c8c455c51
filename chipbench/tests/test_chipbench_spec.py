"""Every cell of BENCHMARK.json resolves to files of its own, and the file
keeps to the benchmark's contract on names, units and keys."""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import compare, spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.resolve(BENCH, cell, ROOT)
    assert c.chips in (1, 4)
    assert c.config["chips"] == c.chips == c.config["num_shards"]
    assert set(c.limits) == set(compare.NUMBERS)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names
        assert callable(spec.load_reader(m["name"]))


def test_names_units_and_entry_keys():
    seen = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for text in (e.get("why"), e.get("layer"), e.get("source")):
            if text is not None:
                assert 1 <= len(text) <= 200 and "\n" not in text
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        assert len({e["name"] for e in BENCH[group]}) == len(BENCH[group])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2
    )


@pytest.mark.parametrize("cell", CELLS)
def test_program_takes_policy_and_engine_from_the_configuration(cell):
    from chipbench import program

    c = spec.resolve(BENCH, cell, ROOT)
    prog = program.Program(c.config, c.traffic)
    placement = c.config["placement"]
    assert type(prog.policy).__name__ == placement["policy"]
    for key, value in placement.get("params", {}).items():
        assert getattr(prog.policy, key) == value
    assert prog.engine == c.config.get("engine", {})


UNMODELLED = {
    "policy": lambda cfg: cfg["placement"].update(policy="TopKPolicy"),
    "policy_parameter": lambda cfg: cfg["placement"]["params"].update(
        decay=0.5),
    "initial_placement": lambda cfg: cfg["placement"].update(initial="full"),
    "engine_option": lambda cfg: cfg["engine"].update(routing=True),
    "bounded_memory": lambda cfg: cfg.update(replica_memory_bytes=2**30),
}


@pytest.mark.parametrize("change", sorted(UNMODELLED))
def test_reference_refuses_what_it_does_not_model(change):
    from chipbench import reference

    c = spec.resolve(BENCH, CELLS[0], ROOT)
    cfg = json.loads(json.dumps(c.config))
    reference.deployment(cfg, c.traffic)
    UNMODELLED[change](cfg)
    with pytest.raises(ValueError, match="does not model"):
        reference.deployment(cfg, c.traffic)
