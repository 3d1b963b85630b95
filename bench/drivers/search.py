"""Search cells: the population hyperparameter search as a loop of jobs.

A job is one call of ``repro.runtime.PopulationTrainer.fit`` on the seeded
train and eval splits: the program's round driver
(``core.population.train_population``) evaluates the K grid members
(``evaluate_population``: features through the training kernel, the dual
ridge over the beta sweep, NRMSE and accuracy on the eval split), culls
half, refines every member by an epoch of truncated-BP SGD
(``refine_population``, the training kernel and its closed-form VJP) and
evaluates again.  The trainer gets the search's sizes from the
configuration; every other knob stays at its default.

Traffic (``bench/traffic/<name>.json``, ``"kind": "search"``): a closed
loop of jobs back to back on the same splits, each job with a seed of its
own for the cull's jitter.  ``warmup_jobs`` jobs run before the window
opens; the window is whole jobs and ends with the first job to end after
``--seconds``.  Every job does the same multiset of work.

``served_samples_per_s`` counts the member-samples the window's jobs learn
from or predict: K x (the refined samples + each evaluation's train and
eval samples), over the window.

What ``correct`` compares, once the window has closed, on the host's CPU:
the last job's population as it entered its refinement
(``PopulationResult.refined_from``), replayed by the plain reference
(``bench/reference/search.py``).  The rules that pick what is compared
are the module constants below; the traffic gives only how many members.

* Refined members: a seeded sample of ``check.members`` members whose
  reference dual Gram is well conditioned at the beta the program chose
  for them, cond(R~ R~^T + beta I) at most ``COND_MAX`` (at beta 1e-6 the
  float32 factor of a Gram of 803 samples is noise).  Members are tried in
  a seeded order until enough are found, skipping any whose refinement is
  chaotic: the reference's epoch from the member's start, each leaf nudged
  by a relative ``SCREEN_DELTA`` of seeded sign, must move the refined
  leaves by at most ``REFINE_AMPLIFICATION_MAX`` times that (at lr 1.0
  with clipped steps and the box's clamp, a trajectory can amplify
  rounding without bound).
* Grid survivors: a seeded sample of ``check.members`` of the cull's
  survivors, the first ceil(K x survive_frac) slots of ``refined_from``.
  They sit at their grid (p, q), and their (W, b) is the grid
  evaluation's dual ridge solution at the beta it chose, carried through
  the cull.  A survivor is compared where its features are resolved in
  float32, (tr(R~ R~^T) - n) / n at least ``FEATURE_SHARE_MIN`` (at p =
  10^-3.75 they sit in the last bits of an all-ones Gram); where the
  reference's choice of beta is clear, ahead of every other beta by more
  than one eval sample, so that rounding cannot move it; and where the
  reference's solve is well conditioned at every beta of the sweep whose
  factor it finds (a factor that is not finite fails on both sides):
  features nudged by a relative ``SCREEN_DELTA`` of seeded sign move each
  W~ by at most ``SOLVE_AMPLIFICATION_MAX`` times that.  The bias column
  makes cond(R~ R~^T + beta I) about n / beta whatever the features, so
  the Gram's condition number says little about W~; and where a rival
  beta's solve is ill conditioned, the program's score there is not the
  reference's, so a lead over it proves nothing.
* The job's best member (``best_p``, ``best_q``, its readout
  ``best_params`` and ``best_nrmse``, usually from the grid).

The readings:

* ``refine_err``: the worst relative error, ||program - reference|| /
  ||reference||, of a refined leaf (p, q, W, b) of a sampled member;
* ``solve_err``: the worst relative error of a survivor's readout [W, b]
  against the reference's ridge solution at the reference's beta;
* ``eval_gap``: the worst relative gap of an eval NRMSE: a sampled
  member's at the program's refined (p, q), over the betas of the sweep at
  which its Gram is well conditioned, and the best member's against the
  NRMSE its readout gets on the reference's features (its beta may be
  ill conditioned: this leaves the solve out and keeps the features, the
  predictions and the eval split);
* ``beta_mismatch``: the compared members whose beta disagrees with the
  reference's.  A survivor's beta is the one whose reference solution
  lies nearest its readout.  A refined member counts where its chosen beta
  is well conditioned and its features resolved, and where the reference
  scores that beta below its best among the well-conditioned betas by
  more than one eval sample (a tie, or one sample's argmax decided by
  rounding, is no mismatch).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import List, Optional

import numpy as np

from drivers import fleet
from drivers.fleet import rel_err, worst
from harness import data, search_costs
from harness.common import CompileCounter, log, memory_peak_bytes

# What the check compares (module docstring), fixed here rather than in
# the traffic, which gives only how many members
COND_MAX = 1e5                  # refined members: cond(R~ R~^T + beta I)
FEATURE_SHARE_MIN = 1e-3        # (tr(R~ R~^T) - n) / n
SCREEN_DELTA = 1e-6             # relative nudge of both screens
REFINE_AMPLIFICATION_MAX = 100  # refined leaves moved / nudge of the start
SOLVE_AMPLIFICATION_MAX = 2e3   # each W~ moved / nudge of the features


def build_cfg(model: dict, search: dict):
    return dataclasses.replace(fleet.build_cfg(model), lr=search["lr"],
                               betas=tuple(search["betas"]))


def job_seeds(seed: int):
    """The jobs' seeds (for the cull's jitter), in job order."""
    rng = data.rng_for(seed, 31)
    while True:
        yield int(rng.integers(0, 2**31 - 1))


def run(cell, seed: int, seconds: float, devices, t_process: float,
        tracer=None, control: bool = False) -> dict:
    import jax
    from repro.core.population import PopulationResult
    from repro.core.types import TimeSeriesBatch
    from repro.runtime import PopulationTrainer, PopulationTrainerConfig

    if "refined_from" not in {f.name for f in
                              dataclasses.fields(PopulationResult)}:
        log("search: this program's PopulationResult has no refined_from, "
            "so no job can be replayed for the check")
        raise SystemExit(2)

    cfg_file, tr = cell.config, cell.traffic
    model, search, spec = (cfg_file["model"], cfg_file["search"],
                           cfg_file["dataset"])
    train, evalb = data.make_dataset(spec, seed)
    log(f"search: {spec['name']} stand-in splits {train.u.shape} and "
        f"{evalb.u.shape} made")
    cfg = build_cfg(model, search)
    on_device = [
        TimeSeriesBatch(u=jax.device_put(s.u, devices[0]),
                        length=jax.device_put(s.length, devices[0]),
                        label=jax.device_put(s.label, devices[0]))
        for s in (train, evalb)]
    trainer = PopulationTrainer(PopulationTrainerConfig(
        divs=search["divs"], rounds=search["rounds"],
        steps_per_round=search["steps_per_round"],
        minibatch=search["minibatch"], survive_frac=search["survive_frac"],
        jitter=search["jitter"]))
    seeds = job_seeds(seed)

    def job():
        res = trainer.fit(cfg, *on_device, seed=next(seeds))
        jax.block_until_ready((res.population, res.final_eval))
        return res

    t_warm = time.perf_counter()
    for _ in range(tr["warmup_jobs"]):
        job()
    log(f"search: warm-up of {tr['warmup_jobs']} job(s) took "
        f"{time.perf_counter() - t_warm:.3f} s")

    counter = CompileCounter()
    best, job_s = [], []
    counter.active = True
    with (tracer() if tracer else contextlib.nullcontext()):
        t_open = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.job"):
                    res = job()
                t1 = time.perf_counter()
                job_s.append(t1 - t0)
                best.append(res.best_nrmse)
                if t1 - t_open >= seconds:
                    break
    counter.active = False
    setup_s = t_open - t_process
    window_s = t1 - t_open
    peak = memory_peak_bytes(devices)
    jobs = len(job_s)
    failed = sum(1 for v in best if not math.isfinite(v))
    log(f"search: window {window_s:.6f} s, jobs {jobs} (median "
        f"{np.median(job_s):.3f} s, slowest {max(job_s):.3f} s), compiles "
        f"in window {counter.count}, memory peak {peak} bytes, best NRMSE "
        f"{res.best_nrmse:.6f}, best accuracy {res.best_acc:.6f}")

    k = int(res.population.p.shape[0])
    per_job = search_costs.job(
        train.length, evalb.length, members=k, nx=model["n_nodes"],
        ny=model["n_classes"], n_in=model["n_in"],
        n_beta=len(search["betas"]), rounds=search["rounds"],
        steps=search["steps_per_round"], minibatch=search["minibatch"])
    e2e = {"served_samples_per_s": jobs * per_job["served"] / window_s,
           "setup_s": setup_s}
    ctx = {"window_s": window_s, "jobs": jobs,
           "search_ops": jobs * per_job["ops"],
           "kernel_ops": jobs * per_job["kernel_ops"],
           "kernel_bytes": jobs * per_job["kernel_bytes"],
           "chips": len(devices), "device_kind": devices[0].device_kind}

    # ---- the check, after the window, from the last job -----------------
    last = jax.device_get({"start": res.refined_from, "final": res.population,
                           "nrmse": res.final_eval.nrmse_all,
                           "beta_idx": res.final_eval.beta_idx})
    bp = jax.device_get(res.best_params)
    last["best"] = {"p": res.best_p, "q": res.best_q, "beta": res.best_beta,
                    "nrmse": res.best_nrmse,
                    "Wt": np.concatenate([bp.W, bp.b[:, None]], -1)}
    del res, on_device, trainer
    readings, control_readings = check(cell, train, evalb, last, seed,
                                       control)
    return {"e2e": e2e, "ctx": ctx, "attempted": jobs, "failed": failed,
            "memory_peak_bytes": peak, "readings": readings,
            "control": control_readings}


def _leaves(params, i: int):
    return tuple(np.asarray(getattr(params, n)[i]) for n in "pqWb")


def _readout(params, i: int) -> np.ndarray:
    """Member i's readout [W, b] (Ny, s)."""
    return np.concatenate([np.asarray(params.W[i]),
                           np.asarray(params.b[i])[:, None]], -1)


def nrmse_gap(got: float, want: float) -> float:
    """Relative gap of two NRMSEs; 0 where both are inf (a failed factor
    on both sides), inf where only one is."""
    if math.isinf(got) and math.isinf(want):
        return 0.0
    if math.isinf(got) or math.isinf(want):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-30)


def nearest_beta(Wt, ref_Wt) -> int:
    """The beta whose reference solution lies nearest a readout (-1 where
    none is finite)."""
    d = [rel_err(Wt, w) if np.isfinite(w).all() else math.inf
         for w in ref_Wt]
    d = [math.inf if math.isnan(x) else x for x in d]
    return int(np.argmin(d)) if math.isfinite(min(d)) else -1


def _scores(r: dict, select: str, n_eval: int):
    """The reference's score of each beta, and the slack within which two
    scores tie: accuracy in eval samples (one sample's argmax may be
    decided by rounding), or NRMSE in the sweep's own units."""
    if select == "acc":
        return np.rint(r["acc"] * n_eval), 1.0
    return -r["nrmse"], 0.0


def _splits(train, evalb, ny: int):
    import jax
    import jax.numpy as jnp

    def triple(s):
        return (jnp.asarray(s.u), jnp.asarray(s.length),
                jax.nn.one_hot(jnp.asarray(s.label), ny, dtype=jnp.float32))
    return triple(train), triple(evalb)


@dataclasses.dataclass
class Member:
    """A compared member: where the program evaluated it, and the
    reference's evaluation there (``reference.search.evaluate``)."""
    name: str
    kind: str           # "refined", "survivor" or "best"
    index: int          # slot in the population (-1: the job's best member)
    p: float
    q: float
    ref: dict
    conds: List[float]  # of the reference Gram, per beta
    well: List[int]     # betas with cond <= COND_MAX
    share: float        # feature_share of the reference Gram
    resolved: bool
    readout_nrmse: Optional[float] = None  # the best member's readout's
    refined: Optional[tuple] = None        # the reference's refined leaves
    amplification: float = math.nan        # of the reference's screen


def _member(name, kind, index, p, q, r, betas) -> Member:
    from reference import search as ref

    conds = [ref.condition(r["gram"], b) for b in betas]
    share = ref.feature_share(r["gram"])
    return Member(name=name, kind=kind, index=index, p=float(p), q=float(q),
                  ref=r, conds=conds,
                  well=[j for j, c in enumerate(conds) if c <= COND_MAX],
                  share=share, resolved=share >= FEATURE_SHARE_MIN)


def clear_choice(r: dict, select: str, n_eval: int) -> bool:
    """Whether the reference's chosen beta leads every other beta by more
    than the slack of ``_scores``."""
    score, slack = _scores(r, select, n_eval)
    j = r["beta_idx"]
    others = [score[k] for k in range(len(score)) if k != j]
    return not others or score[j] - max(others) > slack


def sample_members(cell, train, evalb, last, seed: int) -> List[Member]:
    """The compared members (module docstring), each with the reference's
    evaluation where the program evaluated it, on the host's CPU: the
    seeded sample of the refined population, the seeded sample of the
    grid survivors, then the job's best member."""
    import jax
    import jax.numpy as jnp
    from reference import dfr, search as ref

    cfg_file = cell.config
    model, search = cfg_file["model"], cfg_file["search"]
    n_members = cell.traffic["check"]["members"]
    betas = search["betas"]
    start, final = last["start"], last["final"]
    chosen = np.asarray(last["beta_idx"])
    k = chosen.shape[0]
    n_eval = evalb.length.shape[0]
    kept = []
    with jax.default_device(jax.devices("cpu")[0]):
        mask = dfr.make_mask(model["mask_seed"], model["n_nodes"],
                             model["n_in"])
        tr_, ev_ = _splits(train, evalb, model["n_classes"])

        def evaluate(name, kind, index, p, q):
            r = ref.evaluate(p, q, mask, tr_, ev_, model, betas,
                             search["select"], "highest")
            return _member(name, kind, index, p, q, r, betas)

        refine = ref.refine_epoch_fn(model, cfg_file["train"],
                                     search["minibatch"], "highest")

        def replay(leaves):
            return tuple(np.asarray(x) for x in refine(
                leaves, mask, *tr_, jnp.float32(search["lr"])))

        refined = 0
        for i in data.rng_for(seed, 41).permutation(k):
            if refined == n_members:
                break
            m = evaluate(f"member {i}", "refined", int(i), final.p[i],
                         final.q[i])
            del m.ref["rt"]
            if chosen[i] not in m.well:
                continue
            leaves = _leaves(start, i)
            m.refined = replay(leaves)
            sign = data.rng_for(seed, 43, int(i))
            nudged = tuple(x * (1 + SCREEN_DELTA * sign.choice(
                [-1.0, 1.0], np.shape(x))).astype(np.float32)
                for x in leaves)
            m.amplification = worst(
                [rel_err(a, b) for a, b in zip(replay(nudged), m.refined)]
            ) / SCREEN_DELTA
            if m.amplification <= REFINE_AMPLIFICATION_MAX:
                kept.append(m)
                refined += 1
            else:
                log(f"search: {m.name} not compared: its refinement "
                    f"amplifies a {SCREEN_DELTA:g} change of its start "
                    f"{m.amplification:.3g} times")

        # the cull's survivors, in the program's count
        n_keep = max(1, min(k, math.ceil(k * search["survive_frac"])))
        survivors = 0
        for i in data.rng_for(seed, 47).permutation(n_keep):
            if survivors == n_members:
                break
            m = evaluate(f"survivor {i}", "survivor", int(i), start.p[i],
                         start.q[i])
            rt = m.ref.pop("rt")
            if not (m.resolved
                    and clear_choice(m.ref, search["select"], n_eval)):
                continue
            sign = data.rng_for(seed, 53, int(i)).choice([-1.0, 1.0],
                                                        rt.shape)
            nudged = jnp.asarray((rt * (1 + SCREEN_DELTA * sign))
                                 .astype(np.float32))
            m.amplification = worst([
                rel_err(ref.ridge(nudged, tr_[2], jnp.float32(b),
                                  prec="highest"), w) / SCREEN_DELTA
                for b, w in zip(betas, m.ref["Wt"]) if np.isfinite(w).all()])
            if m.amplification <= SOLVE_AMPLIFICATION_MAX:
                kept.append(m)
                survivors += 1

        best = last["best"]
        m = evaluate("best", "best", -1, best["p"], best["q"])
        del m.ref["rt"]
        m.readout_nrmse = ref.readout_nrmse(m.p, m.q, mask, ev_, best["Wt"],
                                            model, "highest")
        kept.append(m)
    return kept


def program_side(kept, last, betas) -> List[dict]:
    """What the timed job produced for each compared member: a refined
    member's eval NRMSE by beta, its chosen beta and its refined leaves; a
    survivor's readout; the best member's beta and its readout's NRMSE."""
    out = []
    for m in kept:
        i = m.index
        if m.kind == "best":
            best = last["best"]
            out.append({"chosen": betas.index(best["beta"]),
                        "readout_nrmse": best["nrmse"]})
        elif m.kind == "survivor":
            out.append({"Wt": _readout(last["start"], i)})
        else:
            out.append({"nrmse": dict(enumerate(last["nrmse"][i].tolist())),
                        "chosen": int(last["beta_idx"][i]),
                        "refined": _leaves(last["final"], i)})
    return out


def readings_of(kept: List[Member], cand: List[dict], select: str,
                n_eval: int) -> dict:
    """refine_err, solve_err, eval_gap and beta_mismatch (module
    docstring) of one candidate, the program or the control, against the
    reference."""
    errs, solves, gaps, mismatch = [], [], [], 0
    for m, c in zip(kept, cand):
        r = m.ref
        if m.kind == "survivor":
            solves.append(rel_err(c["Wt"], r["Wt"][r["beta_idx"]]))
            mismatch += int(nearest_beta(c["Wt"], r["Wt"]) != r["beta_idx"])
            continue
        if c.get("refined") is not None:
            errs.extend(rel_err(g, w) for g, w in zip(c["refined"],
                                                      m.refined))
        gaps.extend(nrmse_gap(float(v), float(r["nrmse"][j]))
                    for j, v in c.get("nrmse", {}).items() if j in m.well)
        if m.readout_nrmse is not None:
            gaps.append(nrmse_gap(float(c["readout_nrmse"]), m.readout_nrmse))
        if not m.resolved or c["chosen"] not in m.well:
            continue
        score, slack = _scores(r, select, n_eval)
        best = max(score[j] for j in m.well)
        mismatch += int(best - score[c["chosen"]] > slack)
    return {"refine_err": worst(errs), "solve_err": worst(solves),
            "eval_gap": worst(gaps),
            "beta_mismatch": float(mismatch) if kept else math.nan}


def check(cell, train, evalb, last, seed: int, control: bool):
    import jax
    import jax.numpy as jnp
    from reference import dfr, search as ref

    cfg_file = cell.config
    model, search = cfg_file["model"], cfg_file["search"]
    betas = search["betas"]
    t0 = time.perf_counter()
    kept = sample_members(cell, train, evalb, last, seed)

    n_eval = evalb.length.shape[0]
    prog = program_side(kept, last, betas)
    readings = readings_of(kept, prog, search["select"], n_eval)
    log(f"search: checked {len(kept)} members in "
        f"{time.perf_counter() - t0:.3f} s")
    for m, c in zip(kept, prog):
        if m.kind == "survivor":
            j = m.ref["beta_idx"]
            what = (f"reference beta {j}, solve error "
                    f"{rel_err(c['Wt'], m.ref['Wt'][j]):.3g}")
        else:
            what = f"beta {c['chosen']}"
        readout = ("" if m.readout_nrmse is None else
                   f"; readout NRMSE {c['readout_nrmse']!r} (reference "
                   f"{m.readout_nrmse!r})")
        log(f"search: {m.name} p {m.p:.6g} q {m.q:.6g} feature share "
            f"{m.share:.3g} amplification {m.amplification:.3g} {what} "
            f"cond {['%.3g' % x for x in m.conds]} acc "
            f"{m.ref['acc'].tolist()}{readout}")
    ctrl = None
    if control:
        fn = ref.refine_epoch_fn(model, cfg_file["train"], search["minibatch"],
                                 "high")
        cand = []
        with jax.default_device(jax.devices()[0]):
            mask = dfr.make_mask(model["mask_seed"], model["n_nodes"],
                                 model["n_in"])
            tr_, ev_ = _splits(train, evalb, model["n_classes"])
            for m, c in zip(kept, prog):
                r = ref.evaluate(m.p, m.q, mask, tr_, ev_, model, betas,
                                 search["select"], "high")
                if m.kind == "survivor":
                    cand.append({"Wt": r["Wt"][r["beta_idx"]]})
                    continue
                refined = None if m.kind == "best" else tuple(
                    np.asarray(x) for x in fn(
                        _leaves(last["start"], m.index), mask, *tr_,
                        jnp.float32(search["lr"])))
                cand.append({"nrmse": {j: r["nrmse"][j]
                                       for j in c.get("nrmse", {})},
                             "chosen": r["beta_idx"], "refined": refined})
                if m.readout_nrmse is not None:
                    cand[-1]["readout_nrmse"] = ref.readout_nrmse(
                        m.p, m.q, mask, ev_, last["best"]["Wt"], model,
                        "high")
        ctrl = readings_of(kept, cand, search["select"], n_eval)
    return readings, ctrl
