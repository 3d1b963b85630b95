"""Run the system's two main paths once on the chip, at the paper's width.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: the slot-sharded fleet

Configuration: ``configs.dfr_paper.paper_dfr_config("ARAB")`` - Nx = 30,
s = Nx^2 + Nx + 1 = 931, 13 channels, 10 classes, lengths 4-93, on the
seeded Table-4 stand-in from ``repro.data``.

(a) Train-while-serve fleet: ``StreamServer(cfg, t_max=93,
    max_streams=256, window=4)`` with default knobs, so the Pallas serving
    kernel answers every window, serves 512 seeded streams of 64 samples
    (two admission waves, several refresh rounds).  Checks: the kernel's
    logits against ``kernels.ref.streaming_logits_ref`` on the same inputs,
    and the episode's online and held-out accuracy against the plain
    float32 path (``fused_infer=False`` with float32 matmuls) at the same
    seed.
(b) Population training: ``train_population_classification`` at Nx = 30
    on the full-size ARAB stand-in, refining through the fused training
    kernel (``kernels/train.py``) under its custom VJP.  Check: the best
    held-out accuracy against the plain float32 scan path at the same seed.

``--chips 4`` runs only the slot-sharded fleet: the phase (a) episode with
4 x 256 slots and 2048 streams on ``devices=4`` (past one chip's HBM), then
the same episode at 4 x 96 slots on ``devices=4`` and on ``devices=1``,
which must agree under ``repro.runtime.parity``'s rule.  Every device must
serve slots, and the sharded step program must hold no collective.

Earlier lines report the device, compile and step times, peak HBM and each
comparison.  The last line is ``{"ok": true, "device": {...}}``; it is
printed only when every phase and comparison passed.  Without a TPU the
script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

DATASET = "ARAB"
T_MAX = 93
WINDOW = 4
SLOTS = 256             # per chip
#: slots per chip of the --chips 4 parity pair: its one-device run holds
#: all of them, and 4 x 256 needs 17.9 GB of the chip's 15.75 GB (the step
#: program's temporaries are three times its (B, Lt) state)
PARITY_SLOTS = 96
STREAMS_PER_SLOT = 2    # two admission waves
SAMPLES_PER_STREAM = 64  # 8 adaptation windows + 8 accumulation windows
HELD_OUT_STREAMS = 16   # retired models refreshed and scored on the test split
SEED = 0

#: the kernel vs ``streaming_logits_ref`` (float32 matmuls), as in the
#: interpret-mode parity test ``tests/test_kernels.py``
LOGITS_RTOL = LOGITS_ATOL = 1e-4
#: episode accuracy vs the plain float32 path (fractions of samples)
EPISODE_ACC_TOL = 0.02
#: population best held-out accuracy vs the plain float32 scan path
POPULATION_ACC_TOL = 0.03


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(count: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU; JAX found {devices[0].platform}")
    if len(devices) < count:
        raise SystemExit(
            f"chip_smoke: {count} chips asked, {len(devices)} found")
    return devices


def peak_hbm_gib(device) -> float:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 2**30


# ---------------------------------------------------------------------------
# (a) the train-while-serve fleet
# ---------------------------------------------------------------------------


def make_streams(train, n_streams: int, per_stream: int, seed: int):
    """Seeded sensor streams: each draws ``per_stream`` samples of the
    training split (with replacement across streams)."""
    from repro.runtime import StreamRequest

    rng = np.random.default_rng(seed)
    u, ln, lab = (np.asarray(train.u), np.asarray(train.length),
                  np.asarray(train.label))
    idx = rng.integers(0, u.shape[0], size=(n_streams, per_stream))
    return [StreamRequest(rid=r, u=u[i], length=ln[i], label=lab[i])
            for r, i in enumerate(idx)]


def serve_episode(cfg, streams, keep: int, **server_kw):
    """Serve ``streams`` to completion.  Retired models of the first
    ``keep`` streams come back to the host; the rest are dropped so their
    (s, s) snapshots do not pile up in device memory."""
    import jax
    from repro.runtime import StreamServer

    server = StreamServer(cfg, t_max=T_MAX, window=WINDOW, **server_kw)
    for req in streams:
        server.submit(req)
    seen = 0
    t0 = time.perf_counter()
    while server.sched.active():
        server.step()
        for req in server.completed[seen:]:
            req.final_state = (jax.device_get(req.final_state)
                               if req.rid < keep else None)
        seen = len(server.completed)
    server.drain()
    wall = time.perf_counter() - t0
    done = sorted(server.completed, key=lambda r: r.rid)
    assert len(done) == len(streams), (len(done), len(streams))
    times = np.asarray(server.step_times_s) * 1e3
    return {
        "server": server,
        "preds": np.concatenate([np.asarray(r.preds) for r in done]),
        "online_acc": float(np.mean([r.online_accuracy for r in done])),
        "kept": [r.final_state for r in done[:keep]],
        "steps": server.global_step,
        "wall_s": wall,
        "first_step_s": float(times[0]) / 1e3,
        "step_p50_ms": float(np.median(times[1:])),
        "step_max_ms": float(np.max(times[1:])),
    }


def held_out_accuracy(cfg, mask, kept, beta, test) -> float:
    """Mean test accuracy of the kept retired models, each re-solved from
    its own streamed (A, B) statistics."""
    import jax
    import jax.numpy as jnp
    from repro.core import online

    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *kept)
    stacked = online.refresh_output_batched(stacked, beta)
    preds = jax.jit(jax.vmap(
        lambda st: online.online_infer(cfg, mask, st, test.u, test.length)
    ))(stacked)
    return float(jnp.mean((preds == test.label[None]).astype(jnp.float32)))


def check_kernel_logits(cfg, server, streams):
    """Kernel logits vs the float32 reference on one window per slot."""
    import jax
    import jax.numpy as jnp
    from repro.core import masking
    from repro.kernels import ops
    from repro.kernels import ref as kref

    S = server.max_streams
    u = jnp.asarray(np.stack([r.u[:WINDOW] for r in streams[:S]]))
    length = jnp.asarray(np.stack([r.length[:WINDOW] for r in streams[:S]]))
    prm = server.states.params
    j = masking.apply_mask(server.mask, u)
    args = (j, length, prm.p, prm.q, prm.W, prm.b)
    kernel = jax.jit(lambda *a: ops.streaming_logits_slots(
        *a, cfg.n_nodes, f=cfg.f()))
    hlo = kernel.lower(*args).compile().as_text()
    got = np.asarray(kernel(*args))
    ref = jax.jit(jax.vmap(
        lambda *a: kref.streaming_logits_ref(*a, f=cfg.f())))
    with jax.default_matmul_precision("float32"):
        want = np.asarray(ref(*args))
    want_default = np.asarray(ref(*args))
    return {
        "kernel_in_program": "tpu_custom_call" in hlo,
        "max_abs_diff": float(np.max(np.abs(got - want))),
        "max_abs_logit": float(np.max(np.abs(want))),
        "allclose": bool(np.allclose(got, want, rtol=LOGITS_RTOL,
                                     atol=LOGITS_ATOL)),
        "default_precision_max_abs_diff": float(
            np.max(np.abs(want_default - want))),
    }


def phase_serve(cfg, train, test, failures):
    import jax

    streams = make_streams(train, SLOTS * STREAMS_PER_SLOT,
                           SAMPLES_PER_STREAM, SEED)
    log(f"[a] serving {len(streams)} streams x {SAMPLES_PER_STREAM} samples "
        f"on {SLOTS} slots (Nx={cfg.n_nodes}, s={cfg.s}, window={WINDOW})")
    ep = serve_episode(cfg, streams, HELD_OUT_STREAMS, max_streams=SLOTS)
    server = ep["server"]
    if not server.fused_infer:
        failures.append("[a] the server did not pick the fused kernel")
    ep["held_out_acc"] = held_out_accuracy(cfg, server.mask, ep["kept"],
                                           server.beta, test)
    log(f"[a] kernel path: {ep['steps']} steps in {ep['wall_s']:.3f} s; "
        f"first step (compile + run) {ep['first_step_s']:.3f} s; steady "
        f"step p50 {ep['step_p50_ms']:.3f} ms, max {ep['step_max_ms']:.3f} "
        f"ms; online acc {ep['online_acc']:.4f}; held-out acc "
        f"{ep['held_out_acc']:.4f}")
    log(f"[a] peak HBM after the kernel episode: "
        f"{peak_hbm_gib(jax.devices()[0]):.3f} GiB")

    chk = check_kernel_logits(cfg, server, streams)
    log(f"[a] kernel logits vs streaming_logits_ref (float32 matmuls): "
        f"max |diff| {chk['max_abs_diff']:.3e} over max |logit| "
        f"{chk['max_abs_logit']:.3e}; allclose(rtol={LOGITS_RTOL}, "
        f"atol={LOGITS_ATOL}) {chk['allclose']}; tpu_custom_call in program "
        f"{chk['kernel_in_program']}; the same reference at default matmul "
        f"precision is {chk['default_precision_max_abs_diff']:.3e} off")
    if not (chk["allclose"] and chk["kernel_in_program"]):
        failures.append("[a] kernel logits disagree with the reference")
    del server, ep["server"]

    with jax.default_matmul_precision("float32"):
        ref = serve_episode(cfg, make_streams(train, len(streams),
                                              SAMPLES_PER_STREAM, SEED),
                            HELD_OUT_STREAMS, max_streams=SLOTS,
                            fused_infer=False)
        ref["held_out_acc"] = held_out_accuracy(
            cfg, ref["server"].mask, ref["kept"], ref["server"].beta, test)
    agree = float(np.mean(ep["preds"] == ref["preds"]))
    d_on = abs(ep["online_acc"] - ref["online_acc"])
    d_ho = abs(ep["held_out_acc"] - ref["held_out_acc"])
    log(f"[a] plain float32 path: online acc {ref['online_acc']:.4f}, "
        f"held-out acc {ref['held_out_acc']:.4f}; |d online| {d_on:.4f}, "
        f"|d held-out| {d_ho:.4f} (limit {EPISODE_ACC_TOL}); prediction "
        f"agreement {agree:.4f}")
    if max(d_on, d_ho) > EPISODE_ACC_TOL:
        failures.append("[a] episode accuracy departs from the float32 path")


# ---------------------------------------------------------------------------
# (b) population training
# ---------------------------------------------------------------------------


def phase_population(cfg, train, test, failures):
    import jax
    from repro.core import train_population_classification
    from repro.core.population import refine_population

    kw = dict(divs=4, rounds=3, steps_per_round=1, minibatch=4, seed=SEED)
    log(f"[b] population: {kw['divs'] ** 2} members x {kw['rounds']} "
        f"refinement rounds on {train.batch} train / {test.batch} test "
        f"samples (Nx={cfg.n_nodes})")
    t0 = time.perf_counter()
    res = train_population_classification(cfg, train, test, **kw)
    t_fused = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = train_population_classification(cfg, train, test, **kw)
    t_warm = time.perf_counter() - t0

    # the refinement program must hold the training kernel
    y = jax.nn.one_hot(train.label, cfg.n_classes, dtype=cfg.dtype)
    lr = jax.numpy.asarray(cfg.lr, cfg.dtype)
    hlo = refine_population.lower(
        cfg, _mask(cfg), res.population, train.u,
        train.length, y, lr, lr, steps=1, minibatch=kw["minibatch"],
    ).compile().as_text()
    in_program = "tpu_custom_call" in hlo
    log(f"[b] fused kernel path: best held-out acc {res.best_acc:.4f} "
        f"(p={res.best_p:.4f}, q={res.best_q:.4f}, beta={res.best_beta:g}); "
        f"first run (compile + run) {t_fused:.3f} s, second run "
        f"{t_warm:.3f} s; tpu_custom_call in the refinement program "
        f"{in_program}")
    log(f"[b] peak HBM: {peak_hbm_gib(jax.devices()[0]):.3f} GiB")
    if not in_program:
        failures.append("[b] the refinement program holds no kernel")

    with jax.default_matmul_precision("float32"):
        ref = train_population_classification(cfg, train, test, fused=False,
                                              **kw)
    d = abs(res.best_acc - ref.best_acc)
    log(f"[b] plain float32 scan path: best held-out acc {ref.best_acc:.4f}; "
        f"|d| {d:.4f} (limit {POPULATION_ACC_TOL})")
    if d > POPULATION_ACC_TOL:
        failures.append(
            "[b] population accuracy departs from the float32 path")


def _mask(cfg):
    import jax
    from repro.core import masking

    return masking.make_mask(jax.random.PRNGKey(cfg.mask_seed), cfg.n_nodes,
                             cfg.n_in, cfg.dtype)


# ---------------------------------------------------------------------------
# --chips 4: the slot-sharded fleet against one device
# ---------------------------------------------------------------------------


def phase_sharded(cfg, train, chips, failures):
    """The fleet past one HBM (``SLOTS`` per chip on ``devices=chips``),
    then the parity pair: ``PARITY_SLOTS`` per chip served on
    ``devices=chips`` and on one device, which must hold them all."""
    import jax
    from repro.launch.hlo_cost import collectives_in
    from repro.runtime.parity import PARITY_ULPS, ulps_apart

    def run(slots, devices):
        n_streams = slots * STREAMS_PER_SLOT
        ep = serve_episode(cfg, make_streams(train, n_streams,
                                             SAMPLES_PER_STREAM, SEED),
                           HELD_OUT_STREAMS, max_streams=slots,
                           devices=devices)
        server = ep.pop("server")
        if devices > 1:
            served = [int(np.max(np.asarray(sh.data)))
                      for sh in server.states.step.addressable_shards]
            collectives = collectives_in(server.step_program_text())
            log(f"[4] {slots} slots on devices={devices}: steps taken by "
                f"each device's slots {served}; collectives in the step "
                f"program {collectives or 'none'}")
            if min(served) == 0:
                failures.append("[4] a device served no slot")
            if collectives:
                failures.append("[4] the sharded step holds a collective")
        peaks = " ".join(f"{peak_hbm_gib(d):.3f}"
                         for d in jax.devices()[:devices])
        log(f"[4] {n_streams} streams on {slots} slots, devices={devices}: "
            f"{ep['steps']} steps in {ep['wall_s']:.3f} s; first step "
            f"(compile + run) {ep['first_step_s']:.3f} s; steady step p50 "
            f"{ep['step_p50_ms']:.3f} ms, max {ep['step_max_ms']:.3f} ms; "
            f"online acc {ep['online_acc']:.4f}; peak HBM per device "
            f"{peaks} GiB")
        return ep

    run(SLOTS * chips, chips)
    slots = PARITY_SLOTS * chips
    sharded, single = run(slots, chips), run(slots, 1)
    agree = float(np.mean(sharded["preds"] == single["preds"]))
    leaf_ulps = {name: ulps_apart(a, b) for (name, a), (_, b) in zip(
        named_leaves(stack(single["kept"])),
        named_leaves(stack(sharded["kept"])))}
    ulps = max(leaf_ulps.values())
    worst = sorted(leaf_ulps.items(), key=lambda kv: -kv[1])[:6]
    log(f"[4] parity at {slots} slots: predictions agree on {agree:.6f} of "
        f"samples; kept retired models {ulps:.3f} ulps apart (rule: "
        f"predictions exact, <= {PARITY_ULPS} ulps); widest leaves "
        + ", ".join(f"{k} {v:.3f}" for k, v in worst))
    if agree < 1.0 or ulps > PARITY_ULPS:
        failures.append("[4] sharded and single-device episodes disagree")


def stack(states):
    """The kept retired models as one state with a leading model axis."""
    import jax

    return jax.tree_util.tree_map(lambda *x: np.stack(x), *states)


def named_leaves(obj, name="state"):
    """(field path, array) pairs of a state made of dataclasses."""
    if dataclasses.is_dataclass(obj):
        for f in dataclasses.fields(obj):
            yield from named_leaves(getattr(obj, f.name), f"{name}.{f.name}")
    elif obj is not None:
        yield name, obj


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the slot-sharded fleet phase")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = tpu_devices(args.chips)
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {cache}")

    from repro.configs.dfr_paper import paper_dfr_config
    from repro.data import load

    cfg = paper_dfr_config(DATASET)
    t0 = time.perf_counter()
    train, test = load(DATASET, seed=SEED)
    log(f"data: {DATASET} stand-in {train.batch} train / {test.batch} test "
        f"made in {time.perf_counter() - t0:.3f} s")

    failures: list = []
    if args.chips == 1:
        phase_serve(cfg, train, test, failures)
        phase_population(cfg, train, test, failures)
    else:
        phase_sharded(cfg, train, args.chips, failures)
    for f in failures:
        log(f"FAILED {f}")
    if failures:
        raise SystemExit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
