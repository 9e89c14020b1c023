"""The route cells on whatever device JAX has, at a higher rate and a
shorter window than they are timed at, so that windows hold many
requests: a sound run is correct, and the control and each fault the
cell can have make ``correct`` false. The harness's look for a chip is
skipped; the rest of a run is driven as ``bench/run.py`` drives it."""
import time

import jax
import pytest

from bench import harness
from bench.systems import route
from bench.tests import route_faults

CELLS = {"paper_testbed.burst_route": {"base_lam": 200.0},
         "paper_testbed.poisson_b1_route": {"lam": 40.0}}
SEED = 2**31 + 99


def _cell(name):
    cell = harness.find_cell(name)
    cell.traffic = dict(cell.traffic, **CELLS[name])
    return cell


def _run(cell, replace=None):
    line, checks = harness.execute(
        cell, SEED, 1.5, False, time.perf_counter(), jax.devices(),
        system=lambda run: route.run(run, replace=replace))
    return line


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    line = _run(_cell(name))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in
                                    _cell(name).end_to_end}


# a window of one request has no half to leave out
FAULTS = [(name, fault) for name in CELLS
          for fault in ("control", "altered_answer", "half_batch")
          if not (fault == "half_batch" and name.endswith("b1_route"))]


@pytest.mark.parametrize("name,fault", FAULTS)
def test_fault_makes_run_incorrect(name, fault):
    cell = _cell(name)
    make = getattr(route_faults, fault)
    replace = make(cell.config) if fault == "control" else make()
    line = _run(cell, replace)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_wrong_rates_make_run_incorrect(name, monkeypatch):
    """The policy's rates at half what Algorithm 1 reads: the
    reference, recomputing them, counts every row."""
    from repro.control.policies.base import RoutingPolicyBase
    orig = RoutingPolicyBase.lam_matrix

    def lam_matrix(self, reqs, t_now):
        return orig(self, reqs, t_now) * 0.5
    monkeypatch.setattr(RoutingPolicyBase, "lam_matrix", lam_matrix)
    line = _run(_cell(name))
    assert not line["correct"], line["checks"]
    assert line["checks"]["plane_errors"]["value"] > 0
