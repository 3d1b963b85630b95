"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.cholesky import (
    chol_block,
    chol_block_batched,
    trsm_lower,
    trsm_lower_batched,
    trsm_lower_t,
    trsm_lower_t_batched,
)
from repro.kernels.dprr import dprr_pallas
from repro.kernels.ridge_solve import ridge_solve_blocked_batched


@pytest.mark.parametrize("t,nx,block_t", [(128, 30, 64), (300, 30, 128),
                                          (64, 100, 64), (512, 17, 256)])
def test_dprr_kernel_sweep(t, nx, block_t):
    rng = np.random.default_rng(t + nx)
    b = 3
    x = jnp.asarray(rng.normal(size=(b, t, nx)).astype(np.float32))
    lens = jnp.asarray(rng.integers(1, t + 1, b), jnp.int32)
    got = ops.dprr_features(x, lens, nx, block_t=block_t, backend="interpret")
    want = ops.dprr_features(x, lens, nx, block_t=block_t, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("n", [16, 64, 128])
def test_chol_block_sweep(n):
    rng = np.random.default_rng(n)
    M = rng.normal(size=(n, 2 * n)).astype(np.float32)
    a = jnp.asarray(M @ M.T + n * np.eye(n, dtype=np.float32))
    got = chol_block(a, interpret=True)
    want = ref.chol_ref(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("m,n", [(8, 32), (128, 64), (256, 128)])
def test_trsm_kernels_sweep(m, n):
    rng = np.random.default_rng(m * n)
    M = rng.normal(size=(n, 2 * n)).astype(np.float32)
    L = jnp.asarray(np.linalg.cholesky(M @ M.T + n * np.eye(n)).astype(np.float32))
    a = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    got = trsm_lower_t(a, L, block_m=min(128, m), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.trsm_lower_t_ref(a, L)),
                               rtol=2e-3, atol=2e-3)
    got2 = trsm_lower(a, L, block_m=min(128, m), interpret=True)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(ref.trsm_lower_ref(a, L)),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("k,n", [(2, 16), (4, 64)])
def test_chol_block_batched_matches_loop(k, n):
    rng = np.random.default_rng(k * n)
    tiles = []
    for _ in range(k):
        M = rng.normal(size=(n, 2 * n)).astype(np.float32)
        tiles.append(M @ M.T + n * np.eye(n, dtype=np.float32))
    a = jnp.asarray(np.stack(tiles))
    got = chol_block_batched(a, interpret=True)
    for i in range(k):
        want = chol_block(a[i], interpret=True)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("k,m,n", [(2, 8, 32), (3, 128, 64)])
def test_trsm_batched_kernels_match_loop(k, m, n):
    rng = np.random.default_rng(k + m + n)
    Ls, As = [], []
    for _ in range(k):
        M = rng.normal(size=(n, 2 * n)).astype(np.float32)
        Ls.append(np.linalg.cholesky(M @ M.T + n * np.eye(n)).astype(np.float32))
        As.append(rng.normal(size=(m, n)).astype(np.float32))
    L = jnp.asarray(np.stack(Ls))
    a = jnp.asarray(np.stack(As))
    bm = min(128, m)
    got_t = trsm_lower_t_batched(a, L, block_m=bm, interpret=True)
    got = trsm_lower_batched(a, L, block_m=bm, interpret=True)
    for i in range(k):
        np.testing.assert_allclose(
            np.asarray(got_t[i]),
            np.asarray(trsm_lower_t(a[i], L[i], block_m=bm, interpret=True)),
            rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(
            np.asarray(got[i]),
            np.asarray(trsm_lower(a[i], L[i], block_m=bm, interpret=True)),
            rtol=2e-3, atol=2e-3)


def test_ridge_solve_blocked_batched_vs_dense_ref():
    rng = np.random.default_rng(7)
    k, s, ny, block = 3, 100, 5, 64
    As, Bs = [], []
    for _ in range(k):
        R = rng.normal(size=(s, 2 * s)).astype(np.float32)
        Bs.append(R @ R.T + 0.1 * np.eye(s, dtype=np.float32))
        As.append(rng.normal(size=(ny, s)).astype(np.float32))
    A = jnp.asarray(np.stack(As))
    B = jnp.asarray(np.stack(Bs))
    got = ridge_solve_blocked_batched(A, B, block=block, interpret=True)
    for i in range(k):
        want = np.asarray(As[i]) @ np.linalg.inv(np.asarray(Bs[i], np.float64))
        scale = np.max(np.abs(want))
        np.testing.assert_allclose(np.asarray(got[i]) / scale, want / scale,
                                   rtol=0, atol=3e-4)


@pytest.mark.parametrize("s,block", [(100, 64), (300, 128), (257, 128)])
def test_ridge_solve_blocked_sweep(s, block):
    rng = np.random.default_rng(s)
    R = rng.normal(size=(s, 2 * s)).astype(np.float32)
    B = jnp.asarray(R @ R.T + 0.1 * np.eye(s, dtype=np.float32))
    A = jnp.asarray(rng.normal(size=(7, s)).astype(np.float32))
    got = ops.ridge_solve(A, B, block=block, backend="interpret")
    want = ref.ridge_solve_ref(A, B)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale,
                               rtol=0, atol=2e-4)


@pytest.mark.parametrize("t,chunk,f_name", [(64, 32, "linear"), (96, 32, "tanh"),
                                            (128, 128, "tanh")])
def test_reservoir_kernel_sweep(t, chunk, f_name):
    f = {"linear": (lambda z: z), "tanh": jnp.tanh}[f_name]
    rng = np.random.default_rng(t)
    b, nx = 8, 30
    j = jnp.asarray(rng.normal(size=(b, t, nx)).astype(np.float32))
    lens = jnp.asarray(rng.integers(1, t + 1, b), jnp.int32)
    p, q = jnp.float32(0.2), jnp.float32(0.5)
    got = ops.reservoir_states(j, lens, p, q, nx, f=f, chunk_t=chunk,
                               block_b=8, backend="interpret")
    want = ops.reservoir_states(j, lens, p, q, nx, f=f, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_dprr_kernel_single_sample_matches_manual():
    """Direct pallas_call contract (padding semantics) vs ref.dprr_ref."""
    rng = np.random.default_rng(5)
    t_pad, n_pad, nx = 256, 128, 30
    x = jnp.asarray(rng.normal(size=(t_pad, n_pad)).astype(np.float32))
    x = x.at[:, nx:].set(0.0)
    length = jnp.asarray(200, jnp.int32)
    got = dprr_pallas(x, length, nx, block_t=128, interpret=True)
    want = ref.dprr_ref(x, length, nx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("b,t,nx,ny,chunk,f_name",
                         [(3, 50, 30, 4, 64, "linear"),
                          (2, 130, 17, 9, 128, "linear"),
                          (4, 64, 8, 2, 64, "tanh"),
                          (12, 40, 30, 10, 64, "linear")])
def test_streaming_kernel_matches_unfused(b, t, nx, ny, chunk, f_name):
    """Fused streaming step (reservoir -> DPRR -> readout in one kernel)
    vs the unfused XLA composition, across lengths/padding/nonlinearity."""
    f = {"linear": (lambda z: z), "tanh": jnp.tanh}[f_name]
    rng = np.random.default_rng(b * t + nx)
    j = jnp.asarray(rng.normal(size=(b, t, nx)).astype(np.float32))
    lens = jnp.asarray(rng.integers(1, t + 1, b), jnp.int32)
    p, q = jnp.float32(0.02), jnp.float32(0.3)
    W = jnp.asarray(0.01 * rng.normal(size=(ny, nx * (nx + 1))).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(ny,)).astype(np.float32))
    got = ops.streaming_logits(j, lens, p, q, W, bias, nx, f=f,
                               chunk_t=chunk, backend="interpret")
    want = ops.streaming_logits(j, lens, p, q, W, bias, nx, f=f,
                                backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)
