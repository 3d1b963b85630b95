"""Fleet cells: the train-while-serve stream server under seeded traffic.

The window drives ``StreamServer.submit`` and ``StreamServer.step`` of
``repro.runtime``; every step is one dispatch of the pool step program
(window gather, serving kernel, serve step with truncated BP and (A, B),
cond-gated ridge refresh).  The server gets the deployment's sizes and the
model's serving semantics from the configuration; every performance knob
stays at its default.

Traffic (``bench/traffic/<name>.json``, ``"kind": "fleet"``): a closed
loop of ``arrivals.sessions`` sessions, each submitting its next stream as
soon as the previous one retires.  A stream is one speaker's session of
the seeded train split (``harness.data.stream_source``:
``session.utterances_per_class`` utterances of every class, in a seeded
order).  The first ``slots`` streams continue sessions already under way,
so that the fleet retires its streams evenly over the steps.
``warmup_steps`` steps run before the window opens.

What ``correct`` compares, once the window has closed: a seeded reservoir
sample of ``check.streams`` streams that retired in the window, and the
first stream of the longest length that did, each replayed by the plain
reference (``bench/reference/dfr.py``) from the global step at which the
timed run admitted it:

* ``pred_gap``: the widest gap by which the reference's logit of a served
  prediction lies below the reference's best logit, over every sample the
  sampled streams were served;
* ``state_err``: the worst relative error, ||program - reference|| /
  ||reference||, of a leaf of the retired model (p, q, W, b, A, B and the
  sample count) over the sampled streams.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import time
from typing import Dict, List, Optional

import numpy as np

from harness import costs, data
from harness.common import CompileCounter, log, memory_peak_bytes


@dataclasses.dataclass
class Rec:
    """One stream's schedule as the timed run recorded it."""
    rid: int
    idx: np.ndarray
    req: object
    admit: int = -1           # global step number of its first window
    finish: int = -1          # global step number of its last window
    state: object = None      # retired model kept for the check

    @property
    def n(self) -> int:
        return int(self.idx.shape[0])


def build_cfg(model: dict):
    from repro.core.types import DFRConfig

    return DFRConfig(
        n_in=model["n_in"], n_classes=model["n_classes"],
        n_nodes=model["n_nodes"], nonlinearity=model["nonlinearity"],
        alpha=model["alpha"], p_init=model["p_init"], q_init=model["q_init"],
        mask_seed=model["mask_seed"])


class Sampler:
    """Seeded reservoir sample of the streams that retire in the window,
    plus the first retiring stream of the longest length."""

    def __init__(self, seed: int, k: int, longest: int):
        self.rng = data.rng_for(seed, 21)
        self.k, self.longest_n = k, longest
        self.seen = 0
        self.kept: List[Rec] = []
        self.longest: Optional[Rec] = None

    def offer(self, rec: Rec) -> List[Rec]:
        """Keep or drop ``rec``; returns the records dropped."""
        if self.longest is None and rec.n == self.longest_n:
            self.longest = rec
            return []
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append(rec)
            return []
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            out, self.kept[j] = self.kept[j], rec
            return [out]
        return [rec]

    def sample(self) -> List[Rec]:
        return self.kept + ([self.longest] if self.longest else [])


def run(cell, seed: int, seconds: float, devices, t_process: float,
        tracer=None, control: bool = False) -> dict:
    import jax
    from repro.runtime import StreamRequest, StreamServer

    cfg_file, tr = cell.config, cell.traffic
    model, serve, spec = cfg_file["model"], cfg_file["serve"], cfg_file["dataset"]
    W = serve["window"]
    train = data.make_split(spec, spec["n_train"], seed, 1)
    log(f"fleet: {spec['name']} stand-in train split {train.u.shape} made")
    cfg = build_cfg(model)
    max_len = max_stream(cell)
    server = StreamServer(
        cfg, t_max=spec["t_max"], max_streams=tr["slots"], window=W,
        devices=len(devices), pool_capacity=max_len, lr=serve["lr"],
        phase_steps=serve["phase_steps"], refresh_every=serve["refresh_every"],
        beta=serve["beta"])

    source = data.stream_source(
        seed, train.label, spec["n_classes"],
        tr["session"]["utterances_per_class"], W, in_progress=tr["slots"])
    recs: Dict[int, Rec] = {}
    order: List[Rec] = []
    sampler = Sampler(seed, tr["check"]["streams"], max_len)
    state = {"admitted": 0, "done": 0, "in_window": False}
    step_end: Dict[int, float] = {}
    step_dur: List[float] = []

    def submit() -> None:
        st = next(source)
        req = StreamRequest(rid=st.rid, u=train.u[st.idx],
                            length=train.length[st.idx],
                            label=train.label[st.idx])
        rec = Rec(rid=st.rid, idx=st.idx, req=req)
        server.submit(req)
        recs[st.rid] = rec
        order.append(rec)

    def one_step() -> None:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            server.step()
        t1 = time.perf_counter()
        g = server.global_step
        step_end[g] = t1
        if state["in_window"]:
            step_dur.append(t1 - t0)
        with jax.profiler.TraceAnnotation("bench.record"):
            n_adm = len(order) - len(server.sched.queue)
            for rec in order[state["admitted"]:n_adm]:
                rec.admit = g
            state["admitted"] = n_adm
            done = server.completed
            fresh = done[state["done"]:]
            state["done"] = len(done)
            for req in fresh:
                rec = recs[req.rid]
                rec.finish = g
                dropped = [rec]
                if state["in_window"]:
                    rec.state = req.final_state
                    dropped = sampler.offer(rec)
                for d in dropped:
                    d.state = None
                    d.req.final_state = None
        with jax.profiler.TraceAnnotation("bench.submit"):
            for _ in fresh:
                submit()

    t_warm = time.perf_counter()
    for _ in range(tr["arrivals"]["sessions"]):
        submit()
    for _ in range(tr["warmup_steps"]):
        one_step()
    jax.block_until_ready(server.states)
    log(f"fleet: warm-up of {tr['warmup_steps']} steps took "
        f"{time.perf_counter() - t_warm:.3f} s; fused kernel "
        f"{server.fused_infer}; {len(server.sched.live())} live, "
        f"{len(server.sched.queue)} queued")

    counter = CompileCounter()
    g_start = server.global_step
    state["in_window"] = True
    counter.active = True
    with (tracer() if tracer else contextlib.nullcontext()):
        t_open = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.perf_counter() - t_open < seconds:
                one_step()
    counter.active = False
    setup_s = t_open - t_process
    g_end = server.global_step
    t_close = step_end[g_end]
    window_s = t_close - t_open
    state["in_window"] = False
    peak = memory_peak_bytes(devices)
    dispatch = list(server.dispatch_times_s)[-(g_end - g_start):]
    log(f"fleet: window {window_s:.6f} s, steps {g_end - g_start}, compiles "
        f"in window {counter.count}, memory peak {peak} bytes; queue "
        f"{len(server.sched.queue)} at close")

    # ---- what the window served, from the recorded schedule -------------
    nx, ny = model["n_nodes"], model["n_classes"]
    every, phase = serve["refresh_every"], serve["phase_steps"]
    s_dim = nx * nx + nx + 1
    served = attempted = failed = 0
    fleet_ops = 0.0
    kern_lengths: List[int] = []
    kern_windows = 0
    for rec in order:
        if rec.admit < 0:
            continue
        n_win = -(-rec.n // W)
        ks = [k for k in range(n_win) if g_start < rec.admit + k <= g_end]
        if not ks:
            continue
        attempted += 1
        if rec.finish >= 0 and len(rec.req.preds) != rec.n:
            failed += 1
        lens = train.length[rec.idx]
        for k in ks:
            g = rec.admit + k
            n_k = min(W, rec.n - k * W)
            served += n_k
            win_lens = [int(x) for x in lens[k * W:k * W + n_k]]
            kern_lengths.extend(win_lens)
            kern_windows += 1
            fleet_ops += sum(costs.fleet_sample_ops(t, nx, ny, k < phase)
                             for t in win_lens)
            if g % every == 0 and k >= phase:
                fleet_ops += costs.ridge_refresh_ops(s_dim, ny)
    k_ops, k_bytes = costs.streaming_kernel(kern_lengths, nx, ny, kern_windows)

    e2e = {
        "served_samples_per_s": served / window_s,
        "setup_s": setup_s,
    }
    log(f"fleet: served {served} samples of {attempted} streams in the "
        f"window; step p50 {1e3 * float(np.median(step_dur)):.3f} ms, "
        f"slowest {1e3 * max(step_dur):.3f} ms")
    ctx = {
        "window_s": window_s, "steps": g_end - g_start,
        "dispatch_s": dispatch, "fleet_ops": fleet_ops,
        "kernel_ops": k_ops, "kernel_bytes": k_bytes,
        "chips": len(devices), "device_kind": devices[0].device_kind,
    }

    # ---- the check, after the program's state is freed -------------------
    kept = []
    for rec in sampler.sample():
        kept.append((rec, jax.device_get(rec.state)))
        rec.state = rec.req.final_state = None
    n_check = len(kept)
    del server, recs, order, sampler
    gc.collect()
    readings, control_readings = check(cell, train, kept, control)
    log(f"fleet: checked {n_check} retired streams "
        f"(lengths {[r.n for r, _ in kept]})")
    return {"e2e": e2e, "ctx": ctx, "attempted": attempted,
            "failed": failed, "memory_peak_bytes": peak,
            "readings": readings, "control": control_readings}


def max_stream(cell) -> int:
    """Samples in the cell's longest stream, a whole number of windows."""
    w = cell.config["serve"]["window"]
    n = data.session_samples(cell.config["dataset"],
                             cell.traffic["session"]["utterances_per_class"])
    return -(-n // w) * w


def _stream_inputs(train, rec: Rec, w: int, max_len: int):
    """The stream's samples padded to ``max_len`` the way the server pads
    its pool rows (u = 0, length = 1, label = 0)."""
    u = np.zeros((max_len,) + train.u.shape[1:], np.float32)
    length = np.ones((max_len,), np.int32)
    label = np.zeros((max_len,), np.int32)
    u[:rec.n] = train.u[rec.idx]
    length[:rec.n] = train.length[rec.idx]
    label[:rec.n] = train.label[rec.idx]
    return u, length, label


def replay(cell, train, kept, prec: str, device):
    """Reference logits and final models of the kept streams."""
    import jax
    import jax.numpy as jnp
    from reference import dfr

    cfg_file = cell.config
    model, serve = cfg_file["model"], cfg_file["serve"]
    w = serve["window"]
    max_len = max_stream(cell)
    fn = dfr.fleet_stream_fn(model, serve, cfg_file["train"], prec)
    out = []
    with jax.default_device(device):
        mask = dfr.make_mask(model["mask_seed"], model["n_nodes"], model["n_in"])
        for rec, _ in kept:
            u, length, label = _stream_inputs(train, rec, w, max_len)
            logits, final = fn(mask, jnp.asarray(u), jnp.asarray(length),
                               jnp.asarray(label), jnp.int32(rec.n),
                               jnp.int32(rec.admit))
            out.append((np.asarray(logits), dfr.to_numpy(final)))
    return out


def readings_of(kept, preds_of, states_of, ref) -> dict:
    """pred_gap and state_err (see the module docstring) of one candidate
    (the program, or the control) against the reference."""
    gaps, errs = [], []
    for i, (rec, _) in enumerate(kept):
        logits, final = ref[i]
        preds = np.asarray(preds_of(i), np.int64)[:rec.n]
        lg = logits[:rec.n]
        gaps.append(np.max(lg.max(-1) - lg[np.arange(rec.n), preds]))
        for got, want in zip(states_of(i), final):
            errs.append(rel_err(got, want))
    return {"pred_gap": worst(gaps), "state_err": worst(errs)}


def rel_err(got, want) -> float:
    """||got - want|| / ||want|| in float64 (NaN when either holds one)."""
    want = np.asarray(want, np.float64)
    d = np.linalg.norm(np.asarray(got, np.float64) - want)
    return float(d / max(np.linalg.norm(want), 1e-30))


def worst(values) -> float:
    """The largest reading; NaN if any reading is NaN (or there is none)."""
    v = np.asarray(values, np.float64)
    return float(np.max(v)) if v.size else math.nan


def _program_state(st):
    return (st.params.p, st.params.q, st.params.W, st.params.b,
            st.ridge.A, st.ridge.B, st.ridge.count)


def check(cell, train, kept, control: bool):
    import jax

    cpu = jax.devices("cpu")[0]
    ref = replay(cell, train, kept, "highest", cpu)
    readings = readings_of(kept, lambda i: kept[i][0].req.preds,
                           lambda i: _program_state(kept[i][1]), ref)
    ctrl = None
    if control:
        low = replay(cell, train, kept, "high", jax.devices()[0])
        ctrl = readings_of(kept, lambda i: np.argmax(low[i][0], -1),
                           lambda i: low[i][1], ref)
    return readings, ctrl
