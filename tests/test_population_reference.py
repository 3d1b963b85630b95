"""One round of the population search against the plain reference.

``PopulationTrainer.fit`` runs the program's round driver (grid
evaluation, cull, one epoch of truncated-BP SGD, evaluation) at a tiny
size on the CPU; ``bench/reference/search.py`` (written from the paper,
importing nothing of the program) replays the round from the population
that entered the refinement (``PopulationResult.refined_from``).  Also
here: that field is what ``refine_population`` received, and the round
driver's ``search.*`` spans and their stats under a profiler.
"""
import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import population
from repro.core.types import DFRConfig, TimeSeriesBatch
from repro.runtime import PopulationTrainer, PopulationTrainerConfig

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from reference import dfr  # noqa: E402
from reference import search as ref  # noqa: E402

NX, N_IN, NY = 4, 3, 3
N_TRAIN, N_EVAL, T_MIN, T_MAX = 18, 6, 2, 12     # 24 samples; 18 < s = 21
DIVS, MINIBATCH = 2, 4                             # K = 4; 16 of 18 refined
CFG = DFRConfig(n_in=N_IN, n_classes=NY, n_nodes=NX, nonlinearity="linear")
MODEL = {"nonlinearity": "linear", "alpha": 1.0}
TRAIN = {"grad_clip": 1.0, "p_range_log10": [-3.75, -0.25],
         "q_range_log10": [-2.75, -0.25]}
EPS32 = 2.0 ** -24

#: refined (p, q, W, b) of a member: program and reference run the same
#: float32 operations in another reduction order over 4 SGD steps of
#: recurrences of at most 12 steps (measured 1.9e-6 here, under 6e-6 at
#: Nx 30 and T 994); the reference at three-pass bf16 departs by up to
#: 4e-3 here and has to fail.
REFINE_RTOL = 2e-5
#: eval NRMSE at one beta: the dual solve's forward error grows with the
#: condition number of R~ R~^T + beta I, so the tolerance is that bound,
#: n eps cond with n = 18 the factored size, plus a floor of 32 eps for
#: the features and the prediction.  Where n eps cond reaches 1 the
#: float32 factor carries no correct digit (it may fail on one side and
#: not the other), and that beta is not compared.
NRMSE_N = N_TRAIN


def _split(rng, n):
    length = rng.integers(T_MIN, T_MAX + 1, n).astype(np.int32)
    u = rng.normal(size=(n, T_MAX, N_IN)).astype(np.float32)
    for i in range(n):
        u[i, length[i]:] = 0.0
    label = (np.arange(n) % NY).astype(np.int32)
    rng.shuffle(label)
    # a class-dependent offset, so that the readout has something to learn
    u += 0.5 * (label[:, None, None] - 1.0) * (u != 0)
    return TimeSeriesBatch(u=u, length=length, label=label)


def _fit(seed=11):
    rng = np.random.default_rng(20260418)
    train, evalb = _split(rng, N_TRAIN), _split(rng, N_EVAL)
    trainer = PopulationTrainer(PopulationTrainerConfig(
        divs=DIVS, rounds=1, steps_per_round=1, minibatch=MINIBATCH))
    return trainer.fit(CFG, train, evalb, seed=seed), train, evalb


@pytest.fixture(scope="module")
def fitted():
    return _fit()


def _triple(split):
    return (jnp.asarray(split.u), jnp.asarray(split.length),
            jax.nn.one_hot(jnp.asarray(split.label), NY, dtype=jnp.float32))


def _leaves(params, i):
    return tuple(np.asarray(getattr(params, n)[i]) for n in "pqWb")


def _rel(got, want):
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / max(np.linalg.norm(want), 1e-30))


def _refined(res, train, prec):
    fn = ref.refine_epoch_fn(MODEL, TRAIN, MINIBATCH, prec)
    mask = dfr.make_mask(CFG.mask_seed, NX, N_IN)
    return [fn(_leaves(res.refined_from, i), mask, *_triple(train),
               jnp.float32(CFG.lr)) for i in range(DIVS ** 2)]


def _refine_errors(res, train, prec):
    return [[_rel(g, w) for g, w in zip(_leaves(res.population, i), want)]
            for i, want in enumerate(_refined(res, train, prec))]


def _nrmse_excess(res, train, evalb, prec):
    """Per member and beta: the relative NRMSE gap over its tolerance
    (above 1 fails); NaN where the beta is not compared."""
    mask = dfr.make_mask(CFG.mask_seed, NX, N_IN)
    out = []
    for i in range(DIVS ** 2):
        r = ref.evaluate(res.population.p[i], res.population.q[i], mask,
                         _triple(train), _triple(evalb), MODEL, CFG.betas,
                         "acc", prec)
        row = []
        for j, beta in enumerate(CFG.betas):
            got, want = float(res.final_eval.nrmse_all[i, j]), r["nrmse"][j]
            bound = NRMSE_N * EPS32 * ref.condition(r["gram"], beta)
            if bound >= 1.0:
                row.append(np.nan)
                continue
            gap = 0.0 if got == want else abs(got - want) / want
            row.append(gap / (bound + 32 * EPS32))
        out.append(row)
    return np.asarray(out)


def test_refined_leaves_match_the_reference(fitted):
    res, train, _ = fitted
    errs = np.asarray(_refine_errors(res, train, "highest"))
    assert errs.max() <= REFINE_RTOL, errs


def test_eval_nrmse_matches_at_every_beta(fitted):
    res, train, evalb = fitted
    excess = _nrmse_excess(res, train, evalb, "highest")
    compared = excess[np.isfinite(excess)]
    assert compared.size >= DIVS ** 2 and compared.max() <= 1.0, excess


def test_the_tolerances_fail_the_reference_at_three_pass_bf16(fitted):
    """The reference computed at the precision below the configuration's
    (every dot as three bf16 passes) fails at least one tolerance."""
    res, train, evalb = fitted
    refine = np.asarray(_refine_errors(res, train, "high"))
    excess = _nrmse_excess(res, train, evalb, "high")
    assert refine.max() > REFINE_RTOL or np.nanmax(excess) > 1.0, (
        refine, excess)


def test_refined_from_is_what_refinement_received(monkeypatch):
    received = []
    real = population.refine_population

    def spy(cfg, mask, pop, *args, **kw):
        received.append(jax.tree_util.tree_map(np.asarray, pop))
        return real(cfg, mask, pop, *args, **kw)

    monkeypatch.setattr(population, "refine_population", spy)
    res, _, _ = _fit(seed=5)
    assert len(received) == 1
    for got, want in zip(jax.tree_util.tree_leaves(res.refined_from),
                         jax.tree_util.tree_leaves(received[0])):
        np.testing.assert_array_equal(np.asarray(got), want)


def test_no_round_leaves_refined_from_empty():
    rng = np.random.default_rng(3)
    train, evalb = _split(rng, N_TRAIN), _split(rng, N_EVAL)
    res = PopulationTrainer(PopulationTrainerConfig(divs=DIVS, rounds=0)).fit(
        CFG, train, evalb)
    assert res.refined_from is None


def _search_spans(log_dir):
    """[(name, start_ns, end_ns, stats)] of the search.* host events."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    assert len(path) == 1, path
    out = []
    for plane in jax.profiler.ProfileData.from_file(path[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("search."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One fit under a profiler: its search.* spans, the result, splits."""
    log_dir = tmp_path_factory.mktemp("search_trace")
    _fit()                                   # compile outside the trace
    with jax.profiler.trace(str(log_dir)):
        res, train, evalb = _fit()
    return _search_spans(log_dir), res, train, evalb


def test_search_spans_and_their_stats(traced):
    spans, res, train, evalb = traced
    names = [s[0] for s in spans]
    assert names.count("search.evaluate") == 2
    assert names.count("search.select") == 3
    assert names.count("search.refine") == 1
    assert names.count("search.round") == 1
    k = DIVS ** 2
    (rnd,) = [s for s in spans if s[0] == "search.round"]
    assert rnd[3]["round"] == 1 and rnd[3]["members"] == k
    inside = [s[0] for s in spans if rnd[1] <= s[1] and s[2] <= rnd[2]]
    assert inside == ["search.round", "search.select", "search.refine",
                      "search.evaluate", "search.select"]
    n_sgd = N_TRAIN // MINIBATCH * MINIBATCH
    for name, _s, _e, st in spans:
        if name == "search.evaluate":
            assert st["samples"] == k * (N_TRAIN + N_EVAL)
            assert st["real_timesteps"] == k * int(
                train.length.sum() + evalb.length.sum())
            assert st["padded_timesteps"] == k * (N_TRAIN + N_EVAL) * T_MAX
        if name == "search.refine":
            assert st["sgd_steps"] == N_TRAIN // MINIBATCH
            assert st["samples"] == k * n_sgd
            assert st["real_timesteps"] == k * int(train.length[:n_sgd].sum())
            assert st["padded_timesteps"] == k * n_sgd * T_MAX
    assert res.history[-1]["round"] == 1


def test_search_spans_count_kernel_timesteps(traced):
    """By hand: T_MAX 12 runs as one chunk of 16 steps, so nothing is
    sorted and every 8-row block with a live row runs 16 x 8 steps: the
    evaluation's 18 + 6 rows are 3 + 1 blocks, the refinement's 4
    minibatches of 4 one block each."""
    spans, *_ = traced
    k = DIVS ** 2
    seen = set()
    for name, _s, _e, st in spans:
        if name == "search.evaluate":
            assert st["kernel_timesteps"] == k * (3 + 1) * 16 * 8
        if name == "search.refine":
            assert st["kernel_timesteps"] == k * 4 * 16 * 8
        seen.add(name)
    assert {"search.evaluate", "search.refine"} <= seen


def test_search_spans_count_kernel_folds(traced):
    """By hand: one accumulator fold per live 8-row block and 16-step
    chunk, per member: the evaluation's 3 + 1 blocks of one chunk each,
    the refinement's 4 minibatches one block each."""
    spans, *_ = traced
    k = DIVS ** 2
    seen = set()
    for name, _s, _e, st in spans:
        if name == "search.evaluate":
            assert st["kernel_folds"] == k * (3 + 1)
            assert st["kernel_timesteps"] == st["kernel_folds"] * 16 * 8
        if name == "search.refine":
            assert st["kernel_folds"] == k * 4
        seen.add(name)
    assert {"search.evaluate", "search.refine"} <= seen


def test_kernel_timesteps_on_net_lengths():
    """NET's 803 + 534 lengths spread over 50-994 (T 994: chunks of 128,
    1,024 steps): sorted by length, longest first, the 101 + 67 blocks
    hold 462 + 307 live chunks of the 808 + 536 the full grid runs.  The
    refinement's shuffled minibatches of 8 are one block each, not
    sorted: the skip alone saves 4-10 % of their chunks."""
    from repro.kernels import ops

    def spread(n):
        return 50 + (np.arange(n) * 945) // n

    tr, ev = spread(803), spread(534)
    assert ops.train_kernel_timesteps(tr, 994) == 462 * 128 * 8
    assert ops.train_kernel_timesteps(ev, 994) == 307 * 128 * 8
    rng = np.random.default_rng(0)
    batches = rng.permutation(tr)[:800].reshape(100, 8)   # one block each
    chunks = sum(ops.train_kernel_timesteps(x, 994) for x in batches)
    assert 0.90 * 800 * 1024 < chunks < 0.96 * 800 * 1024
    # one block or one chunk: no sort, only the skip
    assert ops.train_kernel_timesteps([0] * 8, 994) == 0
    assert ops.train_kernel_timesteps([5, 129, 0], 994) == 2 * 128 * 8
    assert ops.train_kernel_timesteps([3, 0] * 9, 12) == 3 * 16 * 8
    # one accumulator fold per live block-chunk
    assert ops.train_kernel_folds(tr, 994) == 462
    assert ops.train_kernel_folds(ev, 994) == 307
    assert ops.train_kernel_folds([5, 129, 0], 994) == 2
    assert ops.train_kernel_folds([0] * 8, 994) == 0


def test_spans_compute_nothing_unrecorded(monkeypatch):
    """Without a profiler the stats are never computed."""
    def boom(*a, **k):
        raise AssertionError("stats computed while nothing records")

    monkeypatch.setattr(population, "_timestep_stats", boom)
    _fit()


@pytest.mark.parametrize("program, scopes", [
    ("evaluate", ("search.features", "search.gram", "search.solve",
                  "search.predict")),
    ("refine", ("search.sgd",)),
])
def test_programs_carry_their_scopes(program, scopes):
    rng = np.random.default_rng(1)
    train = _split(rng, N_TRAIN)
    y = jax.nn.one_hot(train.label, NY)
    mask = dfr.make_mask(CFG.mask_seed, NX, N_IN)
    ps, qs = population.grid_candidates(DIVS)
    if program == "evaluate":
        lowered = population.evaluate_population.lower(
            CFG, mask, ps, qs, train.u, train.length, y, train.u,
            train.length, y)
    else:
        pop = population.init_population(CFG, ps, qs)
        lr = jnp.float32(0.1)
        lowered = population.refine_population.lower(
            CFG, mask, pop, train.u, train.length, y, lr, lr,
            minibatch=MINIBATCH)
    text = lowered.compile().as_text()
    for scope in scopes:
        assert f"{scope}/" in text, scope
