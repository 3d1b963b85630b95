"""Fused training forward (kernels.train / backprop.forward_fused, PR 10).

Three layers of parity pin the production training path:

* the custom-VJP backward (paper Eq. 33-36 in closed form) against BOTH
  ``grads_truncated_manual`` (the paper equations, literally) and
  ``grads_truncated`` (autodiff of the stop_gradient objective) - a
  hypothesis battery over shapes, signs of q, ragged lengths and dtypes;
* the interpret-backend Pallas kernel BITWISE against the ``kernels.ref``
  oracle (same op order on padded shapes), and within float32 rounding
  against the per-step outer-product accumulation;
* the call-site contracts: ``online_serve_step(fused=True)``,
  ``refine_population(fused=...)`` and the jit-cache (retrace) regression
  for the identity-cached ``DFRConfig.f()``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backprop as bp
from repro.core import masking, online, population
from repro.core.types import DFRConfig, DFRParams
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.train import train_forward_pallas, train_forward_scan

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:          # the CI property lane installs hypothesis;
    HAVE_HYP = False         # bare hosts still run the deterministic sweep

SETTINGS = dict(max_examples=25, deadline=None)


def _setup(nx=6, ny=4, t=9, b=2, seed=0, nonlinearity="tanh",
           dtype=jnp.float32):
    cfg = DFRConfig(n_in=3, n_classes=ny, n_nodes=nx,
                    nonlinearity=nonlinearity)
    key = jax.random.PRNGKey(seed)
    params = DFRParams(
        p=jnp.float32(0.15), q=jnp.float32(0.45),
        W=(0.05 * jax.random.normal(key, (ny, cfg.n_rep))).astype(dtype),
        b=0.01 * jnp.ones(ny, dtype),
    )
    j_seq = jax.random.normal(
        jax.random.PRNGKey(seed + 1), (b, t, nx)
    ).astype(dtype)
    labels = jax.random.randint(jax.random.PRNGKey(seed + 2), (b,), 0, ny)
    onehot = jax.nn.one_hot(labels, ny, dtype=dtype)
    return cfg, params, j_seq, onehot


def _grad_close(g1, g2, rtol, atol):
    for name in ("p", "q", "W", "b"):
        np.testing.assert_allclose(
            np.asarray(getattr(g1, name), np.float32),
            np.asarray(getattr(g2, name), np.float32),
            rtol=rtol, atol=atol, err_msg=name,
        )


# ---------------------------------------------------------------------------
# forward parity
# ---------------------------------------------------------------------------


def test_forward_fused_matches_forward():
    cfg, params, j_seq, _ = _setup(t=17, b=3)
    lengths = jnp.asarray([5, 17, 1], jnp.int32)
    f = cfg.f()
    ref = bp.forward(params, j_seq, f, lengths)
    got = bp.forward_fused(params, j_seq, f, lengths)
    for name in ("logits", "probs", "r", "x_last", "x_prev", "j_last"):
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            rtol=1e-5, atol=1e-6, err_msg=name,
        )


# ---------------------------------------------------------------------------
# the hypothesis gradient-parity battery (fused VJP vs manual vs autodiff)
# ---------------------------------------------------------------------------


def _check_grad_parity(seed, nx, t, b, q, ragged):
    cfg, params, j_seq, onehot = _setup(nx=nx, t=t, b=b, seed=seed)
    params = DFRParams(p=params.p, q=jnp.float32(q), W=params.W, b=params.b)
    lengths = None
    if ragged:
        lengths = jax.random.randint(
            jax.random.PRNGKey(seed + 3), (b,), 1, t + 1
        ).astype(jnp.int32)
    f = cfg.f()
    fp = lambda z: 1 - jnp.tanh(z) ** 2  # noqa: E731 (unused by the math)
    lm, gm = bp.grads_truncated_manual(params, j_seq, onehot, f, fp, lengths)
    la, ga = bp.grads_truncated(params, j_seq, onehot, f, lengths)
    lf, gf = bp.grads_truncated_fused(params, j_seq, onehot, f, lengths)
    assert float(abs(lf - lm)) < 1e-4 * max(1.0, float(abs(lm)))
    assert float(abs(lf - la)) < 1e-4 * max(1.0, float(abs(la)))
    _grad_close(gf, gm, rtol=2e-4, atol=1e-5)
    _grad_close(gf, ga, rtol=2e-4, atol=1e-5)


if HAVE_HYP:
    @settings(**SETTINGS)
    @given(
        seed=st.integers(0, 2**16),
        nx=st.integers(2, 8),
        t=st.integers(1, 24),
        b=st.integers(1, 4),
        q=st.floats(-0.9, 0.9, allow_nan=False),
        ragged=st.booleans(),
    )
    def test_fused_grads_match_manual_and_autodiff(seed, nx, t, b, q,
                                                   ragged):
        _check_grad_parity(seed, nx, t, b, q, ragged)
else:
    @pytest.mark.parametrize(
        "seed,nx,t,b,q,ragged",
        [(0, 2, 1, 1, 0.4, False), (1, 6, 9, 2, -0.55, True),
         (2, 8, 24, 4, 0.9, True), (3, 3, 16, 3, -0.9, False),
         (4, 5, 12, 4, 0.0, True), (5, 7, 2, 2, 0.7, True)],
    )
    def test_fused_grads_match_manual_and_autodiff(seed, nx, t, b, q,
                                                   ragged):
        _check_grad_parity(seed, nx, t, b, q, ragged)


def test_fused_grads_bf16_track_scan_autodiff():
    """bf16 activations: the closed-form backward and the autodiff path
    share the f32-accumulated forward, so they agree to bf16 resolution."""
    cfg, params, j_seq, onehot = _setup(t=12, b=3, dtype=jnp.bfloat16)
    params = DFRParams(p=jnp.bfloat16(0.15), q=jnp.bfloat16(0.45),
                       W=params.W, b=params.b)
    lengths = jnp.asarray([4, 12, 7], jnp.int32)
    f = cfg.f()
    la, ga = bp.grads_truncated(params, j_seq, onehot, f, lengths)
    lf, gf = bp.grads_truncated_fused(params, j_seq, onehot, f, lengths)
    assert float(abs(lf - la)) < 3e-2 * max(1.0, float(abs(la)))
    _grad_close(gf, ga, rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("t", [7, 8, 9])
def test_fused_grads_at_chunk_boundaries_interpret(t):
    """T = chunk_t - 1 / chunk_t / chunk_t + 1 through the interpret-mode
    Pallas kernel: the boundary latch and the padded-chunk freeze must not
    leak into the gradients."""
    cfg, params, j_seq, onehot = _setup(nx=4, t=t, b=3, seed=t)
    lengths = jnp.asarray([t, max(1, t - 1), 1], jnp.int32)
    f = cfg.f()
    la, ga = bp.grads_truncated(params, j_seq, onehot, f, lengths)
    lf, gf = bp.grads_truncated_fused(
        params, j_seq, onehot, f, lengths,
        backend="interpret", chunk_t=8, block_b=2,
    )
    assert float(abs(lf - la)) < 1e-4 * max(1.0, float(abs(la)))
    _grad_close(gf, ga, rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# kernel-level parity: interpret backend vs the ref.py oracle (bitwise)
# ---------------------------------------------------------------------------


def _padded_operands(nx=5, t=11, b=3, seed=7, q=0.4):
    j_seq = jax.random.normal(jax.random.PRNGKey(seed), (b, t, nx),
                              jnp.float32)
    lengths = jnp.asarray([t, 4, 1][:b], jnp.int32)
    p, qv = jnp.float32(0.3), jnp.float32(q)
    block_b, chunk_t, n_pad = 4, 8, 128
    jp = kops._pad_to(kops._pad_to(kops._pad_to(j_seq, 2, n_pad),
                                   1, chunk_t), 0, block_b)
    Lp, qp = kops._ring_padded(qv, nx, n_pad)
    lens = kops._pad_to(lengths, 0, block_b)
    return jp, Lp, qp, lens, p, qv, nx, block_b, chunk_t


@pytest.mark.parametrize("q", [0.4, -0.55])
def test_interpret_kernel_bitwise_matches_ref_oracle(q):
    jp, Lp, qp, lens, p, qv, nx, block_b, chunk_t = _padded_operands(q=q)
    f = jnp.tanh
    got = train_forward_pallas(jp, Lp, qp, lens, p, qv, nx, f=f,
                               block_b=block_b, chunk_t=chunk_t,
                               interpret=True)
    ref = kref.train_forward_ref(jp, Lp, qp, lens, p, nx, f=f,
                                 chunk_t=chunk_t)
    for g, r, name in zip(got, ref, ("acc", "x_last", "x_prev", "j_last")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=name)


def _skip_lengths(pattern):
    """(lengths, T, chunk_t) of a length pattern for the dead-chunk skip."""
    if pattern == "spread":          # shuffled, several chunks a block
        lens = 1 + (np.arange(40) * 300) // 40
        np.random.default_rng(3).shuffle(lens)
        return lens, 300, 64
    if pattern == "zero_and_one":
        return np.asarray([0, 1, 1, 0, 0, 0, 1, 0, 1, 1, 0, 0, 0, 0, 0,
                           0]), 24, 8
    if pattern == "chunk_edges":     # exactly at, one below, one above
        return np.asarray([8, 16, 7, 9, 15, 17, 24, 8, 16, 16, 16, 16, 16,
                           16, 16, 16]), 24, 8
    if pattern == "dead_block":      # rows 8-15 all padding: n_live 0
        return np.asarray([20] * 8 + [0] * 8 + [3, 11, 5, 2, 9, 1, 4, 6]), \
            24, 8
    if pattern == "ragged_b":        # B = 13, the last block half padding
        return np.asarray([5, 24, 13, 1, 9, 17, 2, 22, 8, 3, 16, 11, 7]), \
            24, 8
    assert pattern == "full"         # nothing to skip
    return np.full(16, 24), 24, 8


def _logical(out, b, nx):
    """The wrapper's logical outputs from the kernel's padded ones."""
    acc, x_last, x_prev, j_last = (np.asarray(o) for o in out)
    r = np.concatenate([acc[:b, :nx, :nx].reshape(b, nx * nx),
                        acc[:b, :nx, nx]], axis=-1)
    return r, x_last[:b, :nx], x_prev[:b, :nx], j_last[:b, :nx]


@pytest.mark.parametrize("pattern", ["spread", "zero_and_one", "chunk_edges",
                                     "dead_block", "ragged_b", "full"])
def test_interpret_kernel_skips_dead_chunks_bitwise(pattern):
    """The kernel skips each block's time chunks past its longest row, and
    the wrapper sorts the rows by length first: both return bit for bit
    what the oracle's full time loop returns on the unsorted operands."""
    lens, t, chunk_t = _skip_lengths(pattern)
    b, nx, block_b = lens.size, 5, 8
    j_seq = jax.random.normal(jax.random.PRNGKey(b + t), (b, t, nx),
                              jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    p, q, f = jnp.float32(0.3), jnp.float32(-0.45), jnp.tanh
    jp = kops._pad_to(kops._pad_to(kops._pad_to(j_seq, 2, 128), 1, chunk_t),
                      0, block_b)
    Lp, qp = kops._ring_padded(q, nx, 128)
    lp = kops._pad_to(lengths, 0, block_b)
    ref = kref.train_forward_ref(jp, Lp, qp, lp, p, nx, f=f,
                                 chunk_t=chunk_t)
    got = train_forward_pallas(jp, Lp, qp, lp, p, q, nx, f=f,
                               block_b=block_b, chunk_t=chunk_t,
                               interpret=True)
    for g, r, name in zip(got, ref, ("acc", "x_last", "x_prev", "j_last")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=name)
    wrapped = kops.train_forward(j_seq, lengths, p, q, nx, f=f,
                                 backend="interpret", chunk_t=chunk_t,
                                 block_b=block_b)
    for g, r, name in zip(wrapped, _logical(ref, b, nx),
                          ("r", "x_last", "x_prev", "j_last")):
        np.testing.assert_array_equal(np.asarray(g), r, err_msg=name)


def _per_step_forward(jp, Lp, qp, lens, p, nx, f):
    """The accumulation the kernel's chunk fold replaces: one
    x(k) . [x(k-1), 1]^T outer product added every step, per sample."""
    n_pad = jp.shape[-1]
    col = jnp.arange(n_pad)[None, :]

    def one(jb, length):
        def step(carry, inp):
            x_prev, acc, x_bnd, j_bnd = carry
            j_k, k = inp
            a = p * f(j_k + x_prev)
            x_k = jax.lax.dot(a, Lp.T) + x_prev[:, -1:] * qp[None, :]
            is_bnd = k == length - 1
            x_bnd = jnp.where(is_bnd, x_prev, x_bnd)
            j_bnd = jnp.where(is_bnd, j_k, j_bnd)
            live = k < length
            x_k = jnp.where(live, x_k, x_prev)
            x1m = jnp.where((col < nx) & live, x_k, 0.0)
            x0_aug = jnp.where(col < nx, x_prev,
                               jnp.where(col == nx, 1.0, 0.0))
            return (x_k, acc + x1m[0][:, None] * x0_aug[0][None, :],
                    x_bnd, j_bnd), None

        z = jnp.zeros((1, n_pad), jnp.float32)
        (x_last, acc, x_bnd, j_bnd), _ = jax.lax.scan(
            step, (z, jnp.zeros((n_pad, n_pad), jnp.float32), z, z),
            (jb[:, None, :], jnp.arange(jb.shape[0], dtype=jnp.int32)))
        return acc, x_last[0], x_bnd[0], j_bnd[0]

    return jax.vmap(one)(jp, lens)


@pytest.mark.parametrize("chunk_t", [8, 128])
def test_interpret_kernel_fold_matches_per_step_accumulation(chunk_t):
    """The chunk fold against the per-step accumulation, with lengths at
    the chunk seams (chunk_t - 1, chunk_t, chunk_t + 1), 0, 1 and the full
    window: the accumulator within float32 rounding of the summation
    order, the boundary rows bit for bit."""
    t, nx, block_b = 2 * chunk_t + 5, 5, 8
    lens = np.asarray([chunk_t - 1, chunk_t, chunk_t + 1, 0, 1, t,
                       2 * chunk_t, 3, t, chunk_t + 1, 0, chunk_t - 1])
    b = lens.size
    j_seq = jax.random.normal(jax.random.PRNGKey(chunk_t), (b, t, nx),
                              jnp.float32)
    p, q, f = jnp.float32(0.3), jnp.float32(0.5), jnp.tanh
    jp = kops._pad_to(kops._pad_to(kops._pad_to(j_seq, 2, 128), 1, chunk_t),
                      0, block_b)
    Lp, qp = kops._ring_padded(q, nx, 128)
    lp = kops._pad_to(jnp.asarray(lens, jnp.int32), 0, block_b)
    got = train_forward_pallas(jp, Lp, qp, lp, p, q, nx, f=f,
                               block_b=block_b, chunk_t=chunk_t,
                               interpret=True)
    want = _per_step_forward(jp, Lp, qp, lp, p, nx, f)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6, err_msg="acc")
    for g, w, name in zip(got[1:], want[1:], ("x_last", "x_prev", "j_last")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def _sort_operands(fn, *args):
    """Shapes of the operands of every sort in ``fn``'s jaxpr."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                yield tuple(eqn.invars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def _sort_case(b=24, t=50, seed=2):
    j_seq = jax.random.normal(jax.random.PRNGKey(seed), (b, t, 4),
                              jnp.float32)
    lengths = jax.random.randint(jax.random.PRNGKey(seed + 1), (b,), 0,
                                 t + 1).astype(jnp.int32)
    return j_seq, lengths


def _fwd(j, ln, p, q):
    return kops.train_forward(j, ln, p, q, 4, f=jnp.tanh,
                              backend="interpret", chunk_t=16)


def test_train_forward_sort_commutes_with_a_batch_permutation():
    j_seq, lengths = _sort_case()
    p, q = jnp.float32(0.25), jnp.float32(0.5)
    perm = np.random.default_rng(0).permutation(j_seq.shape[0])
    base = _fwd(j_seq, lengths, p, q)
    moved = _fwd(j_seq[perm], lengths[perm], p, q)
    for a, m, name in zip(base, moved, ("r", "x_last", "x_prev", "j_last")):
        np.testing.assert_array_equal(np.asarray(a)[perm], np.asarray(m),
                                      err_msg=name)


def test_train_forward_vmapped_over_members_matches_member_calls():
    """The evaluation's form: members vmapped over shared inputs.  The
    sort runs once on the (B,) lengths, not per member."""
    j_seq, lengths = _sort_case()
    ps = jnp.asarray([0.1, 0.25, 0.4, 0.7], jnp.float32)
    qs = jnp.asarray([0.5, -0.3, 0.05, 0.8], jnp.float32)
    vfwd = jax.vmap(_fwd, in_axes=(None, None, 0, 0))
    got = vfwd(j_seq, lengths, ps, qs)
    for k in range(ps.size):
        for g, w in zip(got, _fwd(j_seq, lengths, ps[k], qs[k])):
            np.testing.assert_array_equal(np.asarray(g)[k], np.asarray(w))
    assert _sort_operands(vfwd, j_seq, lengths, ps, qs) == [(24,), (24,)]


@pytest.mark.parametrize("b, t, sorted_", [
    (24, 50, True),      # three blocks, four chunks
    (8, 50, False),      # one block: the refinement's minibatch
    (24, 16, False),     # one chunk
])
def test_train_forward_sorts_only_several_blocks_and_chunks(b, t, sorted_):
    j_seq, lengths = _sort_case(b, t)
    p, q = jnp.float32(0.25), jnp.float32(0.5)
    shapes = _sort_operands(_fwd, j_seq, lengths, p, q)
    assert bool(shapes) == sorted_, shapes


def test_interpret_matches_scan_fallback():
    cfg, params, j_seq, _ = _setup(nx=4, t=13, b=5, seed=11)
    lengths = jnp.asarray([13, 1, 7, 13, 2], jnp.int32)
    f = cfg.f()
    scan = kops.train_forward(j_seq, lengths, params.p, params.q, 4,
                              f=f, backend="xla")
    pall = kops.train_forward(j_seq, lengths, params.p, params.q, 4,
                              f=f, backend="interpret", chunk_t=8,
                              block_b=4)
    for s, g, name in zip(scan, pall, ("r", "x_last", "x_prev", "j_last")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(s),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


# ---------------------------------------------------------------------------
# retrace regression: DFRConfig.f() is identity-stable across calls
# ---------------------------------------------------------------------------


def test_cfg_f_identity_stable():
    cfg = DFRConfig(n_in=3, n_classes=4, n_nodes=4, nonlinearity="tanh")
    assert cfg.f() is cfg.f()
    twin = DFRConfig(n_in=5, n_classes=2, n_nodes=8, nonlinearity="tanh")
    assert cfg.f() is twin.f()          # same (nonlinearity, alpha) key


def test_jitted_entry_points_do_not_retrace_on_fresh_f():
    """The silent-retrace audit: repeated calls with ``cfg.f()`` built
    fresh each time must HIT the jit cache of every entry point that takes
    ``f`` statically (run_reservoir, ops.train_forward, ops.
    streaming_logits)."""
    from repro.core import reservoir

    cfg = DFRConfig(n_in=3, n_classes=4, n_nodes=4, nonlinearity="tanh")
    j = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 4), jnp.float32)
    lengths = jnp.asarray([6, 3], jnp.int32)
    p, q = jnp.float32(0.3), jnp.float32(0.4)
    W = jnp.zeros((4, 20), jnp.float32)
    b = jnp.zeros((4,), jnp.float32)

    entry_calls = [
        (reservoir.run_reservoir,
         lambda f: reservoir.run_reservoir(p, q, j, f=f, lengths=lengths)),
        (kops.train_forward,
         lambda f: kops.train_forward(j, lengths, p, q, 4, f=f)),
        (kops.streaming_logits,
         lambda f: kops.streaming_logits(j, lengths, p, q, W, b, 4, f=f)),
    ]
    for entry, call in entry_calls:
        call(DFRConfig(n_in=3, n_classes=4, n_nodes=4).f())
        size = entry._cache_size()
        for _ in range(3):
            call(DFRConfig(n_in=3, n_classes=4, n_nodes=4).f())
        assert entry._cache_size() == size, entry


# ---------------------------------------------------------------------------
# call-site contracts: serve step and population refinement
# ---------------------------------------------------------------------------


def test_online_serve_step_fused_matches_unfused():
    cfg = DFRConfig(n_in=3, n_classes=4, n_nodes=5, nonlinearity="tanh")
    mask = masking.make_mask(jax.random.PRNGKey(1), cfg.n_nodes, cfg.n_in,
                             cfg.dtype)
    state = online.init_state(cfg)
    u = jax.random.normal(jax.random.PRNGKey(2), (3, 9, cfg.n_in), cfg.dtype)
    length = jnp.asarray([9, 4, 1], jnp.int32)
    label = jnp.asarray([0, 2, 1], jnp.int32)
    lr = jnp.asarray(0.1, cfg.dtype)
    weight = jnp.ones((3,), cfg.dtype)
    acc = jnp.asarray(1.0, cfg.dtype)
    out = {}
    for fused in (False, True):
        st, logits, metrics = online.online_serve_step(
            cfg, mask, state, u, length, label, lr, weight, acc, fused=fused
        )
        out[fused] = (st, logits, metrics)
    np.testing.assert_allclose(np.asarray(out[True][1]),
                               np.asarray(out[False][1]),
                               rtol=1e-5, atol=1e-6)
    for leaf_t, leaf_f in zip(jax.tree_util.tree_leaves(out[True][0]),
                              jax.tree_util.tree_leaves(out[False][0])):
        np.testing.assert_allclose(np.asarray(leaf_t), np.asarray(leaf_f),
                                   rtol=1e-4, atol=1e-5)


def test_refine_population_fused_matches_scan_path():
    cfg = DFRConfig(n_in=3, n_classes=3, n_nodes=4, nonlinearity="tanh")
    mask = masking.make_mask(jax.random.PRNGKey(3), cfg.n_nodes, cfg.n_in,
                             cfg.dtype)
    k = jax.random.PRNGKey(4)
    pop = DFRParams(
        p=jnp.asarray([0.2, 0.6], cfg.dtype),
        q=jnp.asarray([0.4, -0.3], cfg.dtype),
        W=0.05 * jax.random.normal(k, (2, cfg.n_classes, cfg.n_rep),
                                   cfg.dtype),
        b=jnp.zeros((2, cfg.n_classes), cfg.dtype),
    )
    u = jax.random.normal(jax.random.PRNGKey(5), (6, 8, cfg.n_in), cfg.dtype)
    lengths = jnp.asarray([8, 5, 8, 2, 8, 8], jnp.int32)
    y = jax.nn.one_hot(jnp.asarray([0, 1, 2, 0, 1, 2]), cfg.n_classes,
                       dtype=cfg.dtype)
    kw = dict(lr_res=jnp.asarray(0.05, cfg.dtype),
              lr_out=jnp.asarray(0.05, cfg.dtype), steps=2, minibatch=3)
    ref_pop, ref_loss = population.refine_population(
        cfg, mask, pop, u, lengths, y, fused=False, **kw)
    got_pop, got_loss = population.refine_population(
        cfg, mask, pop, u, lengths, y, fused=True, **kw)
    np.testing.assert_allclose(np.asarray(got_loss), np.asarray(ref_loss),
                               rtol=1e-4, atol=1e-5)
    for name in ("p", "q", "W", "b"):
        np.testing.assert_allclose(
            np.asarray(getattr(got_pop, name)),
            np.asarray(getattr(ref_pop, name)),
            rtol=1e-4, atol=1e-5, err_msg=name,
        )


def test_scan_fallback_handles_unbatched_and_default_lengths():
    cfg, params, j_seq, _ = _setup(nx=3, t=6, b=1)
    f = cfg.f()
    r_b, xl_b, xp_b, jl_b = train_forward_scan(
        j_seq, None, params.p, params.q, f=f)
    r_s, xl_s, xp_s, jl_s = train_forward_scan(
        j_seq[0], None, params.p, params.q, f=f)
    np.testing.assert_allclose(np.asarray(r_s), np.asarray(r_b[0]),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(xp_s), np.asarray(xp_b[0]),
                               rtol=1e-6, atol=1e-7)
