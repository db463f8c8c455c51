"""The comparison that decides ``correct``, shown to pass and to fail.

A run is driven past the harness's look for a chip, at a size a CPU test
holds (the cell's own deployment and mix with fewer keys and requests),
and held to the cell's committed limits, for every cell of
``BENCHMARK.json``:

* a sound run comes out correct;
* the control (the reference serving every chunk from the map one sweep
  older, which breaks the read guarantee) comes out not correct;
* the timed path broken underneath comes out not correct, once for each
  fault such a cell can have: the daemon's step returning the store
  unchanged, half of each chunk left out of the replay, and one answer
  altered where it is produced.
"""

from __future__ import annotations

import os
import sys
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import compare, reference, run, spec  # noqa: E402
from repro.kvsim import simulate  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from faults import FAULTS  # noqa: E402

SEED = 2**33 + 17
CELLS = [w["name"] for w in spec.load_benchmark(ROOT)["workloads"]]


def small(cell: spec.Cell) -> spec.Cell:
    """The cell at CPU-test size: 2,000 keys, 40 sweeps of 1,000
    requests."""
    return cell._replace(
        config=dict(cell.config, records=2000),
        traffic=dict(cell.traffic, requests_per_call=40_000,
                     requests_per_sweep=1000),
    )


def load(name: str) -> spec.Cell:
    return spec.resolve(spec.load_benchmark(ROOT), name, ROOT)


@pytest.fixture
def fresh_programs():
    """Faults are planted in module globals the jitted engine reads while
    tracing, so compiled programs must not outlive a test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def drive(cell_name: str) -> dict:
    cell = small(load(cell_name))
    return run.run_cell(cell, SEED, 0.2, False, time.perf_counter(),
                        jax.devices()[:1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, fresh_programs):
    result = drive(cell)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    c = small(load(cell))
    d = reference.deployment(c.config, c.traffic)
    r = d.num_requests
    for seed in (1, 2, 3):
        ref = reference.simulate(d, seed)
        ctl = reference.simulate(d, seed, control=True)
        values = compare.numbers(*compare.reference_as_program(ctl, r), ref, r)
        assert not compare.passed(compare.checks(values, c.limits)), values


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, fresh_programs,
                                          monkeypatch):
    name, broken = FAULTS[fault]
    monkeypatch.setattr(simulate, name, broken(getattr(simulate, name)))
    jax.clear_caches()
    result = drive(cell)
    assert not result["correct"], result["checks"]
