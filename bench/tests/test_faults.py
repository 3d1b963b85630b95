"""Each fault a cell can have, planted under the timed path, makes
``correct`` come out false; the unbroken program passes.

The harness runs here without the chip's look (CPU, tiny sizes); every
other part of a run (traffic, window, check against the reference) is the
benchmark's own.  The faults are ``harness.faults``'s; the exchange
between chips has none to plant: the cell's program holds no collective.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import tiny_cell
from drivers import fleet
from harness import faults
from harness.common import judge

CELL = "arab-fleet-saturated"


def run_cell(cpu, seed=20260101, control=False):
    cell = tiny_cell(CELL)
    jax.clear_caches()
    try:
        res = fleet.run(cell, seed, 1.0, cpu, time.perf_counter(),
                        control=control)
    finally:
        jax.clear_caches()
    checks = judge(res["readings"], cell.limits["limits"])
    return all(c["ok"] for c in checks) and res["failed"] == 0, res


def test_fleet_sound(cpu):
    ok, res = run_cell(cpu)
    assert ok, res["readings"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fleet_fault_caught(fault, cpu):
    n_cls = tiny_cell(CELL).config["model"]["n_classes"]
    with faults.planted(fault, n_cls):
        ok, res = run_cell(cpu)
    assert not ok, res["readings"]


def test_control_fails(cpu):
    """The control (the reference at the precision below the
    configuration's) is not correct by the cell's limits."""
    _, res = run_cell(cpu, seed=7, control=True)
    checks = judge(res["control"], tiny_cell(CELL).limits["limits"])
    assert not all(c["ok"] for c in checks), res["control"]
    assert jnp.isfinite(jnp.asarray(list(res["control"].values()))).all()
