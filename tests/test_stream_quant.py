"""Quantized int8 serving: equivalence battery.

The contracts under test (see runtime/stream_server.py module docstring):

  * the fp32 serving path is BITWISE the PR-6 path: predictions and final
    model state of a full multi-admission episode reproduce the committed
    golden fixture in every retirement mode (``quantize='none'`` compiles
    the exact pre-PR-7 program);
  * the int8 episode pipelined at depth 1 or 2 is bit for bit the
    synchronous int8 episode, in every retirement mode;
  * ``quantize='int8'`` changes ONLY the served logits: training,
    statistics and refreshes are bit-for-bit the fp32 episode, slots arm
    at their first ridge-refresh boundary, and the argmax agreement with
    fp32 serving stays inside the measured band;
  * the int8 kernel equals its XLA oracle (integer math is exact, so
    interpret-vs-xla is tight), zero-range windows and bf16 configs are
    NaN-free, and the quantize/dequantize round trip obeys the half-step
    error bound;
  * invalid knob combinations fail loudly at construction.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import masking, online
from repro.core.types import DFRConfig
from repro.kernels import ops
from repro.runtime import StreamRequest, StreamServer

CFG = DFRConfig(n_in=2, n_classes=3, n_nodes=8)

RETIREMENT_MODES = (
    ("none", {}),
    ("none-inc", {"refresh_mode": "incremental"}),
    ("forget", {"refresh_mode": "incremental", "retirement": "forget",
                "forget": 0.9}),
    ("window", {"refresh_mode": "incremental", "retirement": "window",
                "retire_window": 6}),
    ("adaptive", {"refresh_mode": "incremental", "retirement": "adaptive"}),
)

GOLDEN = "tests/golden/stream_fp32_golden.npz"


def _make_stream(rid, n, t=16, seed=0, n_in=2, n_classes=3):
    r = np.random.default_rng(seed)
    return StreamRequest(
        rid=rid,
        u=r.normal(size=(n, t, n_in)).astype(np.float32),
        length=r.integers(4, t + 1, n).astype(np.int32),
        label=r.integers(0, n_classes, n).astype(np.int32),
    )


def _episode_streams(seed0=0):
    return [_make_stream(i, n, seed=seed0 + i)
            for i, n in enumerate([8, 6, 10, 4, 7])]


def _serve(streams=None, cfg=CFG, **kw):
    srv = StreamServer(cfg, t_max=16, max_streams=3, window=2,
                       phase_steps=2, refresh_every=3, **kw)
    for s in (streams if streams is not None else _episode_streams()):
        srv.submit(s)
    done = srv.run_until_drained()
    return {r.rid: list(r.preds) for r in done}, srv


def _assert_states_bitwise_equal(sa, sb):
    for a, b in zip(jax.tree_util.tree_leaves(sa),
                    jax.tree_util.tree_leaves(sb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_states_equal_cross_program(sa, sb):
    """Bitwise on every serving-relevant leaf; loss_ema (diagnostic) to
    ~1 ulp - different XLA programs may fuse its reduction differently
    (the test_stream_pipeline.py idiom)."""
    _assert_states_bitwise_equal(sa.params, sb.params)
    _assert_states_bitwise_equal(sa.ridge, sb.ridge)
    np.testing.assert_array_equal(np.asarray(sa.step), np.asarray(sb.step))
    a = np.asarray(sa.loss_ema, np.float32)
    b = np.asarray(sb.loss_ema, np.float32)
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


def _agreement(pa, pb):
    assert sorted(pa) == sorted(pb)
    total = agree = 0
    for rid in pa:
        assert len(pa[rid]) == len(pb[rid])
        total += len(pa[rid])
        agree += sum(int(x == y) for x, y in zip(pa[rid], pb[rid]))
    return agree / total


# ---------------------------------------------------------------------------
# fp32 regression: bitwise the PR-6 golden fixture
# ---------------------------------------------------------------------------

GOLDEN_MODES = (
    ("none", {}),
    ("none-inc", {"refresh_mode": "incremental"}),
    ("forget", {"refresh_mode": "incremental", "retirement": "forget",
                "forget": 0.9}),
    ("window", {"refresh_mode": "incremental", "retirement": "window",
                "retire_window": 6}),
)
GOLDEN_STATE_LEAVES = (
    ("params_p", lambda s: s.params.p),
    ("params_q", lambda s: s.params.q),
    ("params_W", lambda s: s.params.W),
    ("params_b", lambda s: s.params.b),
    ("ridge_A", lambda s: s.ridge.A),
    ("ridge_B", lambda s: s.ridge.B),
    ("ridge_count", lambda s: s.ridge.count),
    ("ridge_Lt", lambda s: s.ridge.Lt),
    ("ridge_factor_beta", lambda s: s.ridge.factor_beta),
    ("step", lambda s: s.step),
    ("loss_ema", lambda s: s.loss_ema),
)


@pytest.fixture(scope="module")
def golden():
    fix = np.load(GOLDEN, allow_pickle=False)
    if str(fix["jax_version"]) != jax.__version__ or \
            str(fix["platform"]) != jax.default_backend():
        pytest.skip(
            "golden fixture generated on jax "
            f"{fix['jax_version']}/{fix['platform']}; this env is "
            f"{jax.__version__}/{jax.default_backend()} - bitwise pinning "
            "only holds for the exact compiler"
        )
    return fix


@pytest.mark.parametrize("mode,kw", GOLDEN_MODES,
                         ids=[m for m, _ in GOLDEN_MODES])
def test_fp32_serving_is_bitwise_the_pr6_golden(golden, mode, kw):
    """The default-path (quantize='none') episode reproduces
    the pre-PR-7 fixture bit for bit: predictions AND every PR-6 model
    state leaf.  This is the regression gate for 'the fp32 path must stay
    bitwise identical'."""
    preds, srv = _serve(**kw)
    for rid, p in preds.items():
        np.testing.assert_array_equal(
            np.asarray(p, np.int32), golden[f"{mode}/preds/{rid}"]
        )
    for name, get in GOLDEN_STATE_LEAVES:
        np.testing.assert_array_equal(
            np.asarray(get(srv.states)), golden[f"{mode}/state/{name}"],
            err_msg=f"{mode}: state leaf {name} drifted from the PR-6 fixture",
        )


# ---------------------------------------------------------------------------
# int8 pipelining: depth D == depth 0, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,kw", RETIREMENT_MODES,
                         ids=[m for m, _ in RETIREMENT_MODES])
@pytest.mark.parametrize("depth", [1, 2])
def test_int8_pipelined_is_bitwise_synchronous(depth, mode, kw):
    """The quantized episode pipelined at depth 1/2 serves the synchronous
    quantized episode exactly: same predictions, same model state (the
    quantization leaves included) and the same retired models - the lag-D
    ring defers only bookkeeping, and the scale fold rides the same pool
    step program."""
    preds_0, srv_0 = _serve(quantize="int8", **kw)
    preds_d, srv_d = _serve(quantize="int8", pipeline_depth=depth, **kw)
    assert preds_0 == preds_d
    _assert_states_bitwise_equal(srv_0.states, srv_d.states)
    assert srv_0.global_step == srv_d.global_step
    for a, b in zip(sorted(srv_0.completed, key=lambda r: r.rid),
                    sorted(srv_d.completed, key=lambda r: r.rid)):
        assert a.correct == b.correct
        _assert_states_bitwise_equal(a.final_state, b.final_state)


# ---------------------------------------------------------------------------
# int8 serving: training untouched, slots arm, accuracy band
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,kw", RETIREMENT_MODES,
                         ids=[m for m, _ in RETIREMENT_MODES])
def test_int8_serving_never_touches_training(mode, kw):
    """quantize='int8' changes ONLY the served argmax: params, ridge
    statistics, factors and counters are bit-for-bit the fp32 episode in
    every retirement mode (the fast path reads pre-update state; the
    absmax calibration writes only QuantParams)."""
    preds_f, srv_f = _serve(**kw)
    preds_q, srv_q = _serve(quantize="int8", **kw)
    _assert_states_equal_cross_program(srv_f.states, srv_q.states)
    # the measured band: int8 logits rarely flip the argmax at this size
    assert _agreement(preds_f, preds_q) >= 0.9


@pytest.mark.parametrize("mode,kw", RETIREMENT_MODES,
                         ids=[m for m, _ in RETIREMENT_MODES])
def test_scales_fold_at_refresh_boundaries(mode, kw):
    """Slots arm (w_scale, x_scale > 0) once their first cohort refresh
    fires, in every retirement mode; the absmax calibration is live from
    the first served window."""
    srv = StreamServer(CFG, t_max=16, max_streams=2, window=2,
                       phase_steps=2, refresh_every=3, quantize="int8", **kw)
    srv.submit(_make_stream(0, 12, seed=0))
    srv.submit(_make_stream(1, 12, seed=1))
    srv.step()
    assert np.all(np.asarray(srv.states.quant.x_absmax) > 0)
    assert np.all(np.asarray(srv.states.quant.w_scale) == 0)  # unarmed yet
    # phase_steps=2 SGD steps, then the first refresh at global step 3
    for _ in range(5):
        srv.step()
    srv.drain()
    ws = np.asarray(srv.states.quant.w_scale)
    xs = np.asarray(srv.states.quant.x_scale)
    assert np.all(ws > 0), f"{mode}: slots never armed (w_scale={ws})"
    assert np.all(xs > 0)
    wq = np.asarray(srv.states.quant.Wq)
    assert wq.dtype == np.int8 and np.any(wq != 0)
    # the folded codes reproduce W to within one scale step
    W = np.asarray(srv.states.params.W, np.float32)
    np.testing.assert_allclose(
        wq * ws[:, None, None], W, atol=float(ws.max()) * 0.5 + 1e-7
    )
    srv.run_until_drained()


def test_unarmed_slots_serve_fp32():
    """Before the first refresh boundary every prediction comes from the
    fp32 path: an episode truncated before any refresh matches the fp32
    server sample for sample."""
    def run(**kw):
        srv = StreamServer(CFG, t_max=16, max_streams=2, window=2,
                           phase_steps=2, refresh_every=100, **kw)
        srv.submit(_make_stream(0, 8, seed=0))
        srv.submit(_make_stream(1, 8, seed=1))
        done = srv.run_until_drained()
        return {r.rid: list(r.preds) for r in done}

    assert run() == run(quantize="int8")


# ---------------------------------------------------------------------------
# Kernel parity + edge cases
# ---------------------------------------------------------------------------


def _quant_operands(seed=0, nb=3, t=12, nx=8, ny=3, dtype=np.float32):
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(nb, t, CFG.n_in)).astype(dtype)
    mask = masking.make_mask(jax.random.PRNGKey(0), nx, CFG.n_in, u.dtype)
    j = masking.apply_mask(mask, jnp.asarray(u))
    lengths = jnp.asarray(rng.integers(3, t + 1, nb), jnp.int32)
    p, q = jnp.float32(0.4), jnp.float32(0.6)
    nr = nx * (nx + 1)
    W = rng.normal(size=(ny, nr)).astype(np.float32) * 0.05
    w_scale = ops.symmetric_scale(jnp.max(jnp.abs(jnp.asarray(W))))
    Wq = ops.quantize_symmetric(jnp.asarray(W), w_scale)
    b = jnp.asarray(rng.normal(size=(ny,)).astype(np.float32))
    return j, lengths, p, q, W, Wq, w_scale, b, nx


def test_q8_kernel_matches_its_oracle_exactly():
    """Pallas interpret vs the XLA oracle: the integer contract is shared
    op for op, so the two backends agree to fp32 rounding of the final
    dequant (integer intermediate math is exact)."""
    j, lengths, p, q, W, Wq, w_scale, b, nx = _quant_operands()
    x_scale = jnp.float32(0.02)
    out_xla = ops.streaming_logits_q8(
        j, lengths, p, q, Wq, w_scale, x_scale, b, nx, backend="xla")
    out_itp = ops.streaming_logits_q8(
        j, lengths, p, q, Wq, w_scale, x_scale, b, nx, backend="interpret")
    np.testing.assert_allclose(np.asarray(out_itp), np.asarray(out_xla),
                               rtol=1e-6, atol=1e-6)


def test_q8_kernel_pads_batch_past_one_block():
    """B = 12 runs as two 8-row blocks (the batch padded to 16 with
    length-0 rows); the logical (B, ny) logits still match the oracle."""
    j, lengths, p, q, W, Wq, w_scale, b, nx = _quant_operands(nb=12)
    x_scale = jnp.float32(0.02)
    out_xla = ops.streaming_logits_q8(
        j, lengths, p, q, Wq, w_scale, x_scale, b, nx, backend="xla")
    out_itp = ops.streaming_logits_q8(
        j, lengths, p, q, Wq, w_scale, x_scale, b, nx, backend="interpret")
    assert out_itp.shape == (12, W.shape[0])
    np.testing.assert_allclose(np.asarray(out_itp), np.asarray(out_xla),
                               rtol=1e-6, atol=1e-6)


def test_q8_logits_track_fp32_within_band():
    """With calibrated scales the int8 logits stay near the fp32 fused
    logits - the honest quantization-noise band at Nx=8."""
    j, lengths, p, q, W, Wq, w_scale, b, nx = _quant_operands()
    ref = ops.streaming_logits(
        j, lengths, p, q, jnp.asarray(W), b, nx, backend="xla")
    # calibrate the state scale from the actual fp32 trajectory
    from repro.core import reservoir as core_res
    x = core_res.run_reservoir(p, q, j, lengths=lengths)
    x_scale = ops.symmetric_scale(jnp.max(jnp.abs(x)))
    out = ops.streaming_logits_q8(
        j, lengths, p, q, Wq, w_scale, x_scale, b, nx, backend="xla")
    scale = float(jnp.max(jnp.abs(ref))) + 1e-6
    rel = float(jnp.max(jnp.abs(out - ref))) / scale
    assert rel < 0.05, f"int8 logits off by {rel:.3%} of fp32 range"


def test_q8_zero_range_window_is_nan_free():
    """An all-zero input window (zero-range reservoir trajectory) must
    produce finite logits equal to the bias: all codes are zero and the
    epsilon-floored scales dequantize zeros exactly."""
    j = jnp.zeros((2, 6, 8), jnp.float32)
    lengths = jnp.asarray([6, 3], jnp.int32)
    _, _, p, q, W, Wq, w_scale, b, nx = _quant_operands()
    # unarmed scales (0) take the safe-scale path; armed tiny scales the
    # epsilon floor - both must be finite
    for xs, ws in ((jnp.float32(0.0), jnp.float32(0.0)),
                   (ops.symmetric_scale(jnp.float32(0.0)), w_scale)):
        out = ops.streaming_logits_q8(
            j, lengths, p, q, Wq, ws, xs, b, nx, backend="xla")
        assert np.all(np.isfinite(np.asarray(out)))
        np.testing.assert_allclose(
            np.asarray(out), np.broadcast_to(np.asarray(b), out.shape),
            rtol=1e-6, atol=1e-6)


def test_q8_serving_accepts_zero_streams_end_to_end():
    """A stream of all-zero samples serves NaN-free through the quantized
    server (scales floor at epsilon, logits collapse to the bias)."""
    z = StreamRequest(
        rid=0,
        u=np.zeros((6, 16, 2), np.float32),
        length=np.full((6,), 16, np.int32),
        label=np.zeros((6,), np.int32),
    )
    preds, srv = _serve([z, _make_stream(1, 6, seed=1)], quantize="int8")
    assert len(preds[0]) == 6
    for leaf in jax.tree_util.tree_leaves(srv.states.quant):
        assert np.all(np.isfinite(np.asarray(leaf, np.float64)))


def test_bf16_inputs_feed_the_int8_path():
    """A bf16 config serves through quantize='int8' (the wrapper upcasts
    the staged window to f32 for the integer kernel; scales stay f32
    bookkeeping), NaN-free, with the pipelined path agreeing exactly."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    streams = lambda: [_make_stream(0, 6, seed=3), _make_stream(1, 6, seed=4)]
    preds_q, srv = _serve(streams(), cfg=cfg, quantize="int8",
                          refresh_mode="incremental")
    assert srv.states.params.W.dtype == jnp.bfloat16
    assert srv.states.quant.w_scale.dtype == jnp.float32   # fp32 bookkeeping
    assert srv.states.quant.x_absmax.dtype == jnp.float32
    for r in srv.completed:
        assert len(r.preds) == r.n_samples
    preds_p, _ = _serve(streams(), cfg=cfg, quantize="int8",
                        refresh_mode="incremental", pipeline_depth=2)
    assert preds_q == preds_p


# ---------------------------------------------------------------------------
# Round-trip error bound (hypothesis)
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_half_step_bound():
    hypothesis = pytest.importorskip(
        "hypothesis", reason="optional dep: property tests only")
    from hypothesis import given, settings, strategies as st

    @given(seed=st.integers(0, 10_000), scale=st.floats(1e-6, 1e3),
           n=st.integers(1, 64))
    @settings(max_examples=50, deadline=None)
    def check(seed, scale, n):
        """|dequantize(quantize(v)) - v| <= scale/2 for in-range v: the
        defining bound of symmetric round-to-nearest int8."""
        rng = np.random.default_rng(seed)
        v = (rng.uniform(-1.0, 1.0, n) * scale * 127.0).astype(np.float32)
        s = ops.symmetric_scale(jnp.max(jnp.abs(jnp.asarray(v))))
        q = ops.quantize_symmetric(jnp.asarray(v), s)
        rt = ops.dequantize_symmetric(q, s)
        err = np.max(np.abs(np.asarray(rt) - v))
        bound = float(s) * (0.5 + 1e-3)   # half a step + fp32 slack
        assert err <= bound, f"round-trip err {err} > {bound}"

    check()


# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------


def test_invalid_knob_combinations_fail_loudly():
    mk = lambda **kw: StreamServer(CFG, t_max=16, **kw)
    with pytest.raises(ValueError, match="unknown quantize"):
        mk(quantize="int4")
    with pytest.raises(ValueError, match="staging='device'"):
        mk(quantize="int8", staging="host")


def test_fold_quant_rows_scatter_contract():
    """fold_quant_rows arms exactly the eligible rows and leaves the rest
    untouched (padding rows in a staggered cohort must not arm)."""
    state = online.init_state(CFG)
    batched = jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf, (4, *leaf.shape)).copy(), state)
    batched = dataclasses.replace(
        batched,
        quant=dataclasses.replace(
            batched.quant, x_absmax=jnp.asarray([0.5, 0.5, 0.5, 0.5])),
    )
    rows = jnp.asarray([1, 3], jnp.int32)
    el = jnp.asarray([True, False])
    out = online.fold_quant_rows(batched, rows, el)
    ws = np.asarray(out.quant.w_scale)
    assert ws[1] > 0 and ws[0] == 0 and ws[2] == 0 and ws[3] == 0
    assert np.asarray(out.quant.x_scale)[1] > 0
    np.testing.assert_array_equal(np.asarray(out.quant.x_absmax),
                                  np.asarray(batched.quant.x_absmax))
