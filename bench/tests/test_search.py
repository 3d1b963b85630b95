"""The search cell (``drivers.search``) on the CPU at a tiny size: its
result and job count, the served-sample formula, the costs of the search
against hand counts, the metric readers, and each planted fault of
``harness.search_faults`` read as ``correct: false``."""
import copy
import math
import time

import jax
import pytest

from drivers import search
from harness import search_costs, search_faults, trace
from harness.common import Cell, judge, load_reader, read_per_layer

CELL = "net-search"


def tiny_search_cell() -> Cell:
    """The search cell at a size the CPU runs in seconds: Nx 4 (s = 21), 9
    members, 16 train and 32 eval samples of T 2-12 (fewer train samples
    than s, so the program solves in dual form, as at full size; eval
    samples enough that accuracy ties between betas are rare)."""
    cell = copy.deepcopy(Cell(CELL))
    cfg = cell.config
    cfg["model"]["n_nodes"] = 4
    cfg["dataset"].update(n_train=16, n_test=32, t_min=2, t_max=12)
    cfg["search"].update(divs=3)
    cell.traffic["check"] = dict(cell.traffic["check"], members=2)
    return cell


def run_cell(cpu, seed=2**31 + 99, control=False, seconds=0.5,
             tracer=None):
    cell = tiny_search_cell()
    jax.clear_caches()
    try:
        res = search.run(cell, seed, seconds, cpu, time.perf_counter(),
                         control=control, tracer=tracer)
    finally:
        jax.clear_caches()
    checks = judge(res["readings"], cell.limits["limits"])
    return all(c["ok"] for c in checks) and res["failed"] == 0, res


def _served_per_job(cell) -> int:
    cfg = cell.config
    k = cfg["search"]["divs"] ** 2
    n_tr, n_ev = cfg["dataset"]["n_train"], cfg["dataset"]["n_test"]
    mb = cfg["search"]["minibatch"]
    return k * (n_tr // mb * mb + 2 * (n_tr + n_ev))


def test_search_sound_and_counts_its_jobs(cpu):
    ok, res = run_cell(cpu)
    assert ok, res["readings"]
    ctx, e2e = res["ctx"], res["e2e"]
    assert res["attempted"] == ctx["jobs"] >= 1 and res["failed"] == 0
    assert ctx["window_s"] >= 0.5
    assert e2e["served_samples_per_s"] * ctx["window_s"] == pytest.approx(
        ctx["jobs"] * _served_per_job(tiny_search_cell()))
    assert math.isfinite(e2e["setup_s"]) and e2e["setup_s"] > 0
    assert set(res["readings"]) == set(
        tiny_search_cell().limits["limits"])


def test_served_samples_of_a_net_job():
    """K x (800 refined + 2 x (803 train + 534 eval)) = 64 x 3,474."""
    from harness import data

    spec = Cell(CELL).config["dataset"]
    lens = data.spread_lengths(spec["n_train"], spec["t_min"], spec["t_max"])
    ev = data.spread_lengths(spec["n_test"], spec["t_min"], spec["t_max"])
    got = search_costs.job(lens, ev, members=64, nx=30, ny=13, n_in=4,
                           n_beta=4, rounds=1, steps=1, minibatch=8)
    assert got["served"] == 64 * 3474 == 222336


def test_train_kernel_by_hand():
    # one sample of length 3, Nx = 2, two members sharing the inputs:
    # 2 x 3 x 28 operations; bytes: inputs 3 x 2 and a length, then per
    # member r (2 x 3) and three boundary rows (3 x 2)
    ops, nbytes = search_costs.train_kernel([3], members=2, nx=2)
    assert ops == 2 * 3 * 28
    assert nbytes == 4 * (3 * 2 + 1 + 2 * (6 + 6))


def test_job_ops_by_hand():
    # train lengths [3, 2], eval [4]; one member, Nx = 2 (s = 7), Ny = 3,
    # one channel, one beta, one round of one epoch at minibatch 2
    got = search_costs.job([3, 2], [4], members=1, nx=2, ny=3, n_in=1,
                           n_beta=1, rounds=1, steps=1, minibatch=2)
    step = 28
    kernel_t = 2 * (5 + 4) + 5            # two evaluations, one epoch
    assert got["kernel_ops"] == kernel_t * step
    mask = 2 * kernel_t * 1 * 2
    readout = 2 * 3 * 6 + 3
    bp = 3 * 3 + 4 * 3 * 6 + 4 * 4 + 4 * 2 + 2 * (3 * 6 + 3 + 2)
    ridge = (2 * 2 * 2 * 7                 # Gram R~ R~^T
             + 2 ** 3 / 3                  # Cholesky
             + 2 * 2 * 2 * 3               # two triangular solves
             + 2 * 2 * 3 * 7               # W~ = X^T R~
             + 2 * 1 * 3 * 7)              # predictions
    assert got["ops"] == pytest.approx(
        kernel_t * step + mask + 2 * (readout + bp) + 2 * ridge)
    assert got["served"] == 2 + 2 * 3


def _hand_trace():
    """A window of two jobs: two refinement and four evaluation programs,
    the kernel's custom call inside each, and the host's selections."""
    ops = {"jit_refine_population/jvp_jit_train_forward__.17":
           trace.OpTotal(1.0, 200),
           "jit_evaluate_population/vmap_jit_train_forward__.4":
           trace.OpTotal(1.5, 4),
           "jit_evaluate_population/fusion.3": trace.OpTotal(0.5, 4)}
    mods = {"jit_refine_population": trace.OpTotal(1.2, 2),
            "jit_evaluate_population": trace.OpTotal(2.4, 4)}
    dev = trace.DeviceTrace("/device:TPU:0", [(0.0, 3.6)], mods, ops)
    host = [(0.1, 0.2, "search.select"), (1.0, 1.3, "search.select"),
            (5.0, 5.1, "search.select"), (0.0, 4.0, "search.round")]
    return trace.Trace(window=(0.0, 4.0), devices=[dev], host=host)


def test_every_reader_reads_a_trace():
    ctx = {"trace": _hand_trace(), "window_s": 4.0, "jobs": 2,
           "search_ops": 1e12, "kernel_ops": 1e9, "kernel_bytes": 1e6,
           "chips": 1, "device_kind": "TPU v5 lite"}
    cell = Cell(CELL)
    got = read_per_layer(cell, ctx)
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert got["refine_device_ms.search"]["value"] == pytest.approx(600.0)
    assert got["evaluate_device_ms.search"]["value"] == pytest.approx(1200.0)
    assert got["select_host_ms.search"]["value"] == pytest.approx(200.0)
    assert got["idle_share.search"]["value"] == pytest.approx(10.0)
    assert got["mfu.search"]["value"] == pytest.approx(
        100 * 1e12 / 4.0 / 197e12)
    assert got["train_kernel_roofline.search"]["value"] == pytest.approx(
        100 * (1e9 / 197e12) / 2.5)


def test_readers_read_a_recorded_search_window(cpu, tmp_path):
    """The cell's window, recorded on the CPU: the selection spans are
    there, one per evaluation and one per cull."""
    from test_spans import _host_events

    _, res = run_cell(cpu, tracer=lambda: trace.capture(tmp_path / "tr"))
    window, host = _host_events(tmp_path / "tr")
    lo, hi = window
    jobs = res["ctx"]["jobs"]
    n = {name: sum(1 for s, _e, h in host if h == name and lo <= s < hi)
         for name in ("search.select", "search.evaluate", "search.refine",
                      "search.round")}
    assert n == {"search.select": 3 * jobs, "search.evaluate": 2 * jobs,
                 "search.refine": jobs, "search.round": jobs}
    dev = trace.DeviceTrace("/device:TPU:0", [window], {}, {})
    ctx = {"trace": trace.Trace(window=window, devices=[dev], host=host),
           "jobs": jobs}
    value = load_reader("select_host_ms.search")(ctx)
    assert value is not None and value > 0


def test_parent_without_refined_from_ends_at_once(cpu, monkeypatch):
    """A program whose result keeps no refined_from cannot be checked:
    the run ends before any job, with a nonzero exit."""
    import dataclasses

    from repro.core import population

    fields = [f.name for f in dataclasses.fields(population.PopulationResult)
              if f.name != "refined_from"]
    parent = dataclasses.make_dataclass("PopulationResult", fields)
    monkeypatch.setattr(population, "PopulationResult", parent)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as e:
        search.run(tiny_search_cell(), 1, 0.5, cpu, t0)
    assert e.value.code != 0 and time.perf_counter() - t0 < 5.0


def test_survivor_rules_by_hand():
    """A survivor's beta is the one whose reference solution lies nearest
    its readout; the reference's choice is clear only where it leads
    every other beta by more than one eval sample."""
    import numpy as np

    w = [np.full((2, 3), 1.0), np.full((2, 3), 2.0),
         np.full((2, 3), np.nan)]
    assert search.nearest_beta(np.full((2, 3), 1.9), w) == 1
    assert search.nearest_beta(np.full((2, 3), 1.1), w) == 0
    assert search.nearest_beta(np.ones((2, 3)), w[2:]) == -1
    r = {"acc": np.array([0.5, 0.75, 0.7]), "nrmse": np.ones(3),
         "beta_idx": 1}
    assert not search.clear_choice(r, "acc", 20)    # 15 against 14 samples
    assert search.clear_choice(r, "acc", 100)       # 75 against 70


#: the reading that has to catch each fault, finite and over its limit
CAUGHT_BY = {"refine_skipped": "refine_err", "half_epoch": "refine_err",
             "half_eval": "eval_gap", "ridge_beta_x10": "solve_err",
             "readouts_reversed": "solve_err"}


@pytest.mark.parametrize("fault", search_faults.FAULTS)
def test_search_fault_caught(fault, cpu):
    with search_faults.planted(fault):
        ok, res = run_cell(cpu)
    assert not ok, res["readings"]
    name = CAUGHT_BY[fault]
    value = res["readings"][name]
    assert math.isfinite(value), res["readings"]
    assert value > tiny_search_cell().limits["limits"][name], res["readings"]


@pytest.mark.parametrize("seed", [1, 7])
def test_control_reads_worse_than_the_program(cpu, seed):
    """The control (the reference at the precision below the
    configuration's) departs from the reference further than the program
    does.  Whether it fails the cell's limits is a reading of the cell's
    full size on the chip (PERF.md): at Nx 4 and 16 samples the control's
    features carry too little rounding to."""
    _, res = run_cell(cpu, seed=seed, control=True)
    prog, ctrl = res["readings"], res["control"]
    assert ctrl["refine_err"] > 3 * prog["refine_err"], (prog, ctrl)
