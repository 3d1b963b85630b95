"""Ridge regression for the DFR output layer (paper Sec. 2.5 / 3.6).

Solves  W~ = A B^{-1}  with  A = E R~^T (Ny, s),  B = R~ R~^T + beta I (s, s),
s = Nx^2 + Nx + 1.

Four implementations, from paper-faithful to TPU-production:

1. ``ridge_gaussian_numpy``  - Algorithm 1 verbatim (Gauss-Jordan with an
   explicit B^{-1}); the paper's "naive" baseline.  O(2s^3) flops,
   2s(s+Ny)+1 words.
2. ``ridge_cholesky_packed_numpy`` - Algorithms 2/3/4 verbatim: in-place
   Cholesky inside a single 1-D packed array P[s(s+1)/2], then two in-place
   triangular substitutions sharing Q with A/D/W.  s(s+2Ny)/2 + s/2 words.
3. ``ridge_cholesky_packed_jax`` - the same packed in-place algorithm,
   jit-compiled (vectorized inner dot products over contiguous packed rows -
   the packed row-major layout the paper chose is exactly what makes this
   possible).
4. ``ridge_cholesky_blocked`` - the TPU adaptation: right-looking blocked
   Cholesky + blocked TRSMs on 2-D tiles (MXU-aligned); the Pallas kernels in
   ``repro.kernels`` implement the per-tile work, this module carries the
   pure-jnp blocked reference.

Incremental rank-1 engine (``cholupdate_*``): the streaming extension of the
paper's in-place 1-D Cholesky.  Each streamed sample adds one outer product
``r r^T`` to B, so instead of re-factorizing ``B + beta I`` from scratch at
every refresh (O(s^3)), a *live factor* ``L`` is carried next to the (A, B)
statistics and rotated forward per sample with an O(s^2) ``cholupdate``
(hyperbolic variant for the downdate / forgetting path).  A refresh with a
live factor is then just the two triangular substitutions (Algorithms 3/4),
O(s^2 Ny).

When is which path used?

  * **Incremental** (live factor): the continuous-batching stream server in
    ``refresh_mode='incremental'`` - samples arrive rank-1 (small windows),
    the factor is seeded at slot admission as ``sqrt(beta) I`` (B = 0) and
    every accumulated sample rotates it, so no O(s^3) factorization ever
    runs for that slot.  ``repro.core.online.refresh_output`` takes this
    fast path automatically whenever ``RidgeState.factor_beta`` matches the
    requested beta.
  * **Full factorization**: no live factor (offline ridge, the population
    engine, ensemble refresh), a beta different from the seeded one
    (regularization sweeps), or mass accumulation - when many samples land
    between refreshes (large windows / batch admission) the sequential
    rank-1 rotations cost ``n_new * O(s^2)`` with poor arithmetic intensity
    and one blocked/LAPACK O(s^3) factorization wins again; the benchmark's
    honest columns (``bench_stream`` refresh-mode table) quantify the
    crossover.

Memory-word and arithmetic-op count formulas of Tables 2/3 are provided for
the benchmark harness.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import DOT_PRECISION, Array

# ---------------------------------------------------------------------------
# Packed 1-D triangular indexing (paper Eq. 41): P[i(i+1)/2 + j] = B[i][j],
# j <= i, rows stored contiguously.
# ---------------------------------------------------------------------------


def packed_size(s: int) -> int:
    return s * (s + 1) // 2


def packed_index(i, j):
    return i * (i + 1) // 2 + j


def pack_lower(B: Array) -> Array:
    """Dense symmetric (s, s) -> packed 1-D lower triangle P[s(s+1)/2]."""
    s = B.shape[0]
    i, j = np.tril_indices(s)
    return B[(i, j)]


def unpack_lower(P: Array, s: int) -> Array:
    """Packed 1-D -> dense lower-triangular (s, s) (upper = 0)."""
    i, j = np.tril_indices(s)
    out = jnp.zeros((s, s), P.dtype)
    return out.at[(i, j)].set(P)


# ---------------------------------------------------------------------------
# 1. Paper Algorithm 1: Ridge via Gauss-Jordan elimination (the baseline).
# ---------------------------------------------------------------------------


def ridge_gaussian_numpy(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """Verbatim Algorithm 1 (loops and all).  Returns W~ (Ny, s)."""
    A = np.asarray(A, np.float64 if A.dtype == np.float64 else np.float32).copy()
    B = np.array(B, copy=True)
    n_y, s = A.shape
    Binv = np.zeros_like(B)
    for i in range(s):  # lines 1-9: identity init
        Binv[i, i] = 1.0
    for i in range(s):  # lines 10-25: Gauss-Jordan
        buf = 1.0 / B[i, i]
        for j in range(s):
            B[i, j] *= buf
            Binv[i, j] *= buf
        for j in range(s):
            if i != j:
                buf = B[j, i]
                for k in range(s):
                    B[j, k] -= B[i, k] * buf
                    Binv[j, k] -= Binv[i, k] * buf
    W = np.zeros((n_y, s), A.dtype)
    for i in range(n_y):  # lines 26-33
        for j in range(s):
            acc = 0.0
            for k in range(s):
                acc += A[i, k] * Binv[k, j]
            W[i, j] = acc
    return W


@jax.jit
def ridge_gaussian(A: Array, B: Array) -> Array:
    """Algorithm 1 with row operations vectorized (same pivot order, no
    pivot search - B is SPD so the diagonal never vanishes)."""
    s = B.shape[0]
    Binv = jnp.eye(s, dtype=B.dtype)

    def pivot(i, carry):
        B, Binv = carry
        buf = 1.0 / B[i, i]
        brow = B[i] * buf
        binvrow = Binv[i] * buf
        B = B.at[i].set(brow)
        Binv = Binv.at[i].set(binvrow)
        col = B[:, i].at[i].set(0.0)  # eliminate everywhere but the pivot row
        B = B - col[:, None] * brow[None, :]
        Binv = Binv - col[:, None] * binvrow[None, :]
        return B, Binv

    B, Binv = jax.lax.fori_loop(0, s, pivot, (B, Binv))
    return jnp.matmul(A, Binv, precision=DOT_PRECISION)


# ---------------------------------------------------------------------------
# 2. Paper Algorithms 2/3/4 verbatim (numpy reference).
# ---------------------------------------------------------------------------


def cholesky_packed_numpy(P: np.ndarray, s: int) -> np.ndarray:
    """Algorithm 2: in-place Cholesky in the packed 1-D array."""
    P = np.array(P, copy=True)
    for i in range(s):
        for j in range(i):  # lines 2-4: diagonal update
            P[i * (i + 1) // 2 + i] -= P[i * (i + 1) // 2 + j] ** 2
        P[i * (i + 1) // 2 + i] = np.sqrt(P[i * (i + 1) // 2 + i])
        buf = 1.0 / P[i * (i + 1) // 2 + i]
        for j in range(i + 1, s):  # lines 7-12: column below the diagonal
            for k in range(i):
                P[j * (j + 1) // 2 + i] -= P[i * (i + 1) // 2 + k] * P[j * (j + 1) // 2 + k]
            P[j * (j + 1) // 2 + i] *= buf
    return P


def trsm_packed_numpy(Q: np.ndarray, P: np.ndarray, s: int) -> np.ndarray:
    """Algorithm 3: Q (storing A) -> D = A (C^T)^{-1}, in place."""
    Q = np.array(Q, copy=True)
    n_y = Q.shape[0]
    for i in range(n_y):
        for j in range(s):
            for k in range(j):
                Q[i, j] -= Q[i, k] * P[j * (j + 1) // 2 + k]
            Q[i, j] /= P[j * (j + 1) // 2 + j]
    return Q


def trsm_packed_rev_numpy(Q: np.ndarray, P: np.ndarray, s: int) -> np.ndarray:
    """Algorithm 4: Q (storing D) -> W~ = D C^{-1}, in place."""
    Q = np.array(Q, copy=True)
    n_y = Q.shape[0]
    for i in range(n_y):
        for j in range(s - 1, -1, -1):
            for k in range(s - 1, j, -1):
                Q[i, j] -= Q[i, k] * P[k * (k + 1) // 2 + j]
            Q[i, j] /= P[j * (j + 1) // 2 + j]
    return Q


def ridge_cholesky_packed_numpy(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """Paper's full proposed pipeline: pack -> Alg 2 -> Alg 3 -> Alg 4."""
    s = B.shape[0]
    i, j = np.tril_indices(s)
    P = np.ascontiguousarray(np.asarray(B)[(i, j)])
    P = cholesky_packed_numpy(P, s)
    Q = trsm_packed_numpy(np.asarray(A), P, s)
    Q = trsm_packed_rev_numpy(Q, P, s)
    return Q


# ---------------------------------------------------------------------------
# 3. The packed in-place algorithm, jit-compiled.
#
# The key observation that keeps this faithful *and* vectorizable: the paper's
# row-major packed layout makes every inner dot product (Alg 2 line 9, Alg 3
# line 4, Alg 4 line 4) a read over a *contiguous* packed row prefix.  We
# slice fixed-size windows and mask, so the jitted program performs the exact
# same in-place update order over the exact same 1-D array.
# ---------------------------------------------------------------------------


def _row_slice(Ppad: Array, j, s: int) -> Array:
    """Packed row j (length j+1), zero-masked to fixed size s."""
    start = j * (j + 1) // 2
    row = jax.lax.dynamic_slice(Ppad, (start,), (s,))
    return jnp.where(jnp.arange(s) <= j, row, 0.0)


@partial(jax.jit, static_argnames=("s",))
def cholesky_packed_jax(P: Array, s: int) -> Array:
    """Algorithm 2, jitted; P has size s(s+1)/2 (padded internally)."""
    Ppad = jnp.concatenate([P, jnp.zeros((s,), P.dtype)])
    ar = jnp.arange(s)

    def col_i(i, Ppad):
        rowi = _row_slice(Ppad, i, s)
        mask_lt_i = ar < i
        diag = rowi[i] - jnp.sum(jnp.where(mask_lt_i, rowi * rowi, 0.0))
        diag = jnp.sqrt(diag)
        buf = 1.0 / diag
        Ppad = Ppad.at[i * (i + 1) // 2 + i].set(diag)
        rowi = rowi.at[i].set(diag)

        def row_j(j, Ppad):
            rowj = _row_slice(Ppad, j, s)
            dot = jnp.sum(jnp.where(mask_lt_i, rowi * rowj, 0.0))
            val = (rowj[i] - dot) * buf
            return Ppad.at[j * (j + 1) // 2 + i].set(val)

        return jax.lax.fori_loop(i + 1, s, row_j, Ppad)

    Ppad = jax.lax.fori_loop(0, s, col_i, Ppad)
    return Ppad[: packed_size(s)]


@partial(jax.jit, static_argnames=("s",))
def trsm_packed_jax(Q: Array, P: Array, s: int) -> Array:
    """Algorithm 3 jitted: rows of Q solved left-to-right (vectorized over
    the Ny rows, which the FPGA implementation partitions - Alg 5)."""
    Ppad = jnp.concatenate([P, jnp.zeros((s,), P.dtype)])
    ar = jnp.arange(s)

    def col_j(j, Q):
        rowj = _row_slice(Ppad, j, s)  # C[j, :j+1]
        dot = jnp.matmul(Q, jnp.where(ar < j, rowj, 0.0),
                         precision=DOT_PRECISION)  # (Ny,)
        val = (Q[:, j] - dot) / rowj[j]
        return Q.at[:, j].set(val)

    return jax.lax.fori_loop(0, s, col_j, Q)


@partial(jax.jit, static_argnames=("s",))
def trsm_packed_rev_jax(Q: Array, P: Array, s: int) -> Array:
    """Algorithm 4 jitted: W~ = D C^{-1}, columns solved right-to-left.

    Alg 4's inner dot reads C[k, j] for k > j - a packed *column*, which is
    strided.  We read it as a masked gather of P (the same memory, same
    values; the FPGA pays the same BRAM accesses)."""
    ar = jnp.arange(s)
    col_starts = ar * (ar + 1) // 2  # start of each packed row

    def col_j(t, Q):
        j = s - 1 - t
        colj = P[col_starts + j] * (ar >= j)  # C[:, j] masked (k >= j)
        dot = jnp.matmul(Q, jnp.where(ar > j, colj, 0.0),
                         precision=DOT_PRECISION)
        val = (Q[:, j] - dot) / colj[j]
        return Q.at[:, j].set(val)

    return jax.lax.fori_loop(0, s, col_j, Q)


def ridge_cholesky_packed(A: Array, B: Array) -> Array:
    """Jitted packed pipeline (pack -> Alg 2 -> Alg 3 -> Alg 4)."""
    s = B.shape[0]
    i, j = np.tril_indices(s)
    P = B[(i, j)]
    P = cholesky_packed_jax(P, s)
    Q = trsm_packed_jax(A, P, s)
    return trsm_packed_rev_jax(Q, P, s)


# ---------------------------------------------------------------------------
# 4. Blocked (TPU-shaped) Cholesky ridge: pure-jnp reference of the Pallas
#    kernels.  Right-looking, tile-by-tile in-place in a (nb, nb) grid of
#    (bs, bs) tiles - only the lower triangle of tiles is ever touched,
#    preserving the paper's storage insight at tile granularity.
# ---------------------------------------------------------------------------


def _chol_unblocked(a: Array) -> Array:
    """Unblocked lower Cholesky of one tile via vectorized rank-1 updates."""
    n = a.shape[0]
    idx = jnp.arange(n)

    def body(j, a):
        d = jnp.sqrt(a[j, j])
        col = jnp.where(idx > j, a[:, j] / d, 0.0).at[j].set(d)
        a = a.at[:, j].set(jnp.where(idx >= j, col, a[:, j]))
        # trailing update: a[j+1:, j+1:] -= col[j+1:] col[j+1:]^T
        mask = (idx > j).astype(a.dtype)
        upd = (col * mask)[:, None] * (col * mask)[None, :]
        return a - upd

    a = jax.lax.fori_loop(0, n, body, a)
    return jnp.tril(a)


def _trsm_right_lower_t(a: Array, L: Array) -> Array:
    """Solve X L^T = a for X (columns left-to-right), L lower-triangular."""
    n = L.shape[0]

    def body(j, x):
        # only cols < j of x are final; L[j, k>j] = 0
        dot = jnp.matmul(x, L[j, :], precision=DOT_PRECISION)
        # subtract the k == j self term that is not yet valid
        val = (a[:, j] - dot + x[:, j] * L[j, j]) / L[j, j]
        return x.at[:, j].set(val)

    x0 = jnp.zeros_like(a)
    return jax.lax.fori_loop(0, n, body, x0)


def cholesky_blocked_jnp(B: Array, block: int = 128) -> Array:
    """Blocked right-looking Cholesky (reference for the Pallas kernel)."""
    s = B.shape[0]
    pad = (-s) % block
    Bp = jnp.pad(B, ((0, pad), (0, pad)))
    # keep padded diagonal identity so the factorization stays defined
    if pad:
        eye = jnp.eye(s + pad, dtype=B.dtype)
        Bp = Bp + eye * jnp.pad(jnp.zeros((s,), B.dtype), (0, pad), constant_values=1.0)
    n = s + pad
    nb = n // block
    a = Bp
    for kb in range(nb):
        k0 = kb * block
        diag = jax.lax.dynamic_slice(a, (k0, k0), (block, block))
        Lkk = _chol_unblocked(diag)
        a = jax.lax.dynamic_update_slice(a, Lkk, (k0, k0))
        if kb + 1 < nb:
            rest = n - k0 - block
            panel = jax.lax.dynamic_slice(a, (k0 + block, k0), (rest, block))
            Lpanel = _trsm_right_lower_t(panel, Lkk)
            a = jax.lax.dynamic_update_slice(a, Lpanel, (k0 + block, k0))
            trail = jax.lax.dynamic_slice(a, (k0 + block, k0 + block), (rest, rest))
            trail = trail - jnp.matmul(Lpanel, Lpanel.T, precision=DOT_PRECISION)
            a = jax.lax.dynamic_update_slice(a, trail, (k0 + block, k0 + block))
    return jnp.tril(a)[:s, :s]


@jax.jit
def ridge_cholesky_blocked(A: Array, B: Array, block: int = 128) -> Array:
    """Production ridge solve: Cholesky + two triangular solves.

    Never materializes B^{-1}; storage is one triangle + the (Ny, s) Q buffer,
    i.e. the paper's memory claim at tile granularity.  On CPU the factor
    comes from LAPACK potrf; on TPU the Pallas blocked kernels in
    repro.kernels.ridge_solve implement the same pipeline
    (cholesky_blocked_jnp below is their pure-jnp structural reference).
    """
    del block
    C = jnp.linalg.cholesky(B)
    # D = A (C^T)^{-1}  <=>  C D^T = A^T  (forward substitution)
    D = jax.scipy.linalg.solve_triangular(C, A.T, lower=True).T
    # W = D C^{-1}      <=>  C^T W^T = D^T (backward substitution)
    W = jax.scipy.linalg.solve_triangular(C.T, D.T, lower=False).T
    return W


def ridge_cholesky_blocked_ref(A: Array, B: Array, block: int = 128) -> Array:
    """Blocked-tile variant mirroring the Pallas kernel composition."""
    C = cholesky_blocked_jnp(B, block)
    D = jax.scipy.linalg.solve_triangular(C, A.T, lower=True).T
    return jax.scipy.linalg.solve_triangular(C.T, D.T, lower=False).T


def ridge_solve(A: Array, B: Array, method: str = "cholesky_blocked") -> Array:
    """Dispatch: 'gaussian' | 'cholesky_packed' | 'cholesky_blocked'."""
    if method == "gaussian":
        return ridge_gaussian(A, B)
    if method == "cholesky_packed":
        return ridge_cholesky_packed(A, B)
    if method == "cholesky_blocked":
        return ridge_cholesky_blocked(A, B)
    raise ValueError(f"unknown ridge method: {method}")


# ---------------------------------------------------------------------------
# Population-axis (batched) solves: one factorization per population member,
# all in a single XLA program.  These back the vmapped hyperparameter engine
# (repro.core.population); the Pallas tile pipeline has a matching batched
# driver in repro.kernels.ridge_solve.ridge_solve_blocked_batched.
# ---------------------------------------------------------------------------


@jax.jit
def ridge_cholesky_batched(A: Array, B: Array) -> Array:
    """Batched ridge solve:  A (K, Ny, s), B (K, s, s)  ->  W~ (K, Ny, s).

    Same math as ``ridge_cholesky_blocked`` per member (Cholesky + two
    triangular solves, no inverse materialized), with the population axis K
    handled by the batched LAPACK/XLA primitives.
    """
    C = jnp.linalg.cholesky(B)  # (K, s, s), natively batched
    # natively-batched cho_solve (B X = A^T) instead of a vmap of per-member
    # TRSM pairs: one batched triangular-solve primitive for the whole K axis
    # (measurably faster on CPU, where the vmapped path lowers poorly)
    X = jax.scipy.linalg.cho_solve((C, True), jnp.swapaxes(A, -1, -2))
    return jnp.swapaxes(X, -1, -2)


def ridge_solve_batched(A: Array, B: Array, method: str = "cholesky_blocked") -> Array:
    """Population-axis dispatch mirroring ``ridge_solve``.

    A: (K, Ny, s), B: (K, s, s) -> (K, Ny, s).
    """
    if method == "cholesky_blocked":
        return ridge_cholesky_batched(A, B)
    if method == "gaussian":
        return jax.vmap(ridge_gaussian)(A, B)
    raise ValueError(f"unknown batched ridge method: {method}")


# ---------------------------------------------------------------------------
# 5. Incremental rank-1 Cholesky: cholupdate / choldowndate.
#
# Streamed samples perturb B by rank-1 outer products, so the live factor L
# of B + beta I is rotated forward in O(s^2) instead of re-factorized in
# O(s^3): with A = L L^T,
#
#     A + sign * x x^T = L' L'^T
#
# via the LINPACK rotation sweep (sign=+1: Givens-style update; sign=-1:
# hyperbolic downdate, the forgetting-factor / retired-sample path).  Three
# forms, mirroring the factorization section above:
#
#   * ``cholupdate_packed_numpy`` - the paper-shaped oracle: in-place sweep
#     over the same packed 1-D array P[s(s+1)/2] Algorithm 2 factors into
#     (column k of C is the strided packed read the FPGA BRAM pays too).
#   * ``cholupdate_packed_jax``   - the same sweep jitted over the packed
#     array (fori_loop; masked strided column gather/scatter).
#   * ``cholupdate_dense``        - the production form on a dense lower
#     (s, s) factor: the packed addressing defeats the VPU exactly as it
#     defeats the MXU for the factorization (see repro.kernels.cholesky),
#     so the in-state factor is dense-lower and the sweep updates whole
#     columns; ``cholupdate_dense_batched`` vmaps it over a member/slot
#     axis, ``cholupdate_window`` folds a window of samples sequentially.
#     The Pallas tile kernel in ``repro.kernels.cholupdate`` runs the same
#     sweep with the factor resident in VMEM.
#
# The downdate requires  x^T (L L^T)^{-1} x < 1  (the result must stay SPD).
# Degenerate downdates are *guarded*, not NaN-propagated: every rotation
# whose radicand  d_k^2 - x_k^2  falls at or below ``DOWNDATE_GUARD_REL *
# d_k^2`` is skipped entirely (the factor column, diagonal and the rotated x
# are left untouched), so the factor always stays finite, triangular and
# positive-diagonal.  The ``*_guarded`` variants additionally return an
# ``ok`` flag so callers can fall back to a full re-factorization (the
# stream server's sliding-window retirement does exactly that); the
# unflagged forms share the same clamp but silently degrade to "factor no
# longer matches B - x x^T" - documented, tested behavior instead of NaNs.
# The packed numpy *oracle* raises ``numpy.linalg.LinAlgError`` instead:
# as the reference implementation it must never return a silently-wrong
# factor.  The sweeps still assume a positive diagonal on entry (the same
# contract as the factorizations above).
# ---------------------------------------------------------------------------

# Relative radicand floor of the downdate guard: a rotation with
# d_k^2 + sign * x_k^2 <= DOWNDATE_GUARD_REL * d_k^2 is treated as
# indefinite (it would zero or destroy the diagonal in working precision).
# Only reachable for sign=-1: the update radicand is >= d_k^2.
DOWNDATE_GUARD_REL = 1e-6


def _guarded_rotation(dk, xk, sign):
    """One rotation's (r, c, s) with the downdate guard applied.

    Good rotations (radicand > DOWNDATE_GUARD_REL * d_k^2 - every update,
    and every downdate that keeps the diagonal safely positive) compute
    bit-identically to the unguarded sweep.  Bad rotations degrade to the
    exact identity (r = d_k, c = 1, s = 0: column, diagonal and x all
    untouched) and raise the returned ``bad`` flag.  Shared by every jax
    sweep in this module and the Pallas tile kernel in
    ``repro.kernels.cholupdate`` so all forms stay bit-parity-comparable.
    """
    rad = dk * dk + sign * xk * xk
    bad = rad <= DOWNDATE_GUARD_REL * (dk * dk)
    r = jnp.where(bad, dk, jnp.sqrt(jnp.where(bad, jnp.ones_like(rad), rad)))
    c = r / dk
    sk = jnp.where(bad, jnp.zeros_like(xk), xk / dk)
    return r, c, sk, bad


def pad_factor_identity(F: Array, pad: int) -> Array:
    """Zero-pad a (..., s, s) triangular factor by ``pad`` rows/cols with
    ones on the padded diagonal: padded rotations and substitutions become
    exact no-ops instead of zero-pivot divisions.  Shared by the Pallas
    window wrapper (``kernels.ops.cholupdate_window``) and the blocked
    batched substitution below - the invariant lives in one place.
    """
    if not pad:
        return F
    s = F.shape[-1]
    eye_tail = jnp.diag(
        jnp.pad(jnp.zeros((s,), F.dtype), (0, pad), constant_values=1.0)
    )
    widths = ((0, 0),) * (F.ndim - 2) + ((0, pad), (0, pad))
    return jnp.pad(F, widths) + eye_tail.reshape(
        (1,) * (F.ndim - 2) + eye_tail.shape
    )


def seed_factor(s: int, beta, dtype=jnp.float32) -> Array:
    """Factor of the empty system: chol(0 + beta I) = sqrt(beta) I.

    Seeding a fresh slot with this makes every later ``cholupdate`` exact:
    no O(s^3) factorization is ever needed on the incremental path.
    """
    return jnp.sqrt(jnp.asarray(beta, dtype)) * jnp.eye(s, dtype=dtype)


def cholupdate_packed_numpy(P: np.ndarray, x: np.ndarray, s: int,
                            sign: float = 1.0) -> np.ndarray:
    """Rank-1 update of the packed factor, loops and all (the oracle).

    P holds C with C C^T = B (Algorithm 2's output); returns the packed
    factor of B + sign * x x^T.  In-place update order: one rotation per
    column k, touching only packed column k and the tail of x - the same
    storage discipline as Algorithms 2-4.

    Raises ``numpy.linalg.LinAlgError`` on an indefinite downdate (a
    rotation radicand <= 0, i.e. ``x^T (C C^T)^{-1} x >= 1``): the oracle
    never returns a silently-NaN factor.  The production jax forms clamp
    and signal instead (see the section comment / ``*_guarded``).
    """
    P = np.array(P, copy=True)
    x = np.array(x, copy=True).astype(P.dtype)
    for k in range(s):
        dk = P[k * (k + 1) // 2 + k]
        rad = dk * dk + sign * x[k] * x[k]
        if rad <= 0.0:
            raise np.linalg.LinAlgError(
                f"indefinite downdate: rotation {k} radicand {rad!r} <= 0 "
                "(x^T B^{-1} x >= 1; the downdated matrix is not SPD)"
            )
        r = np.sqrt(rad)
        c = r / dk
        sk = x[k] / dk
        P[k * (k + 1) // 2 + k] = r
        for j in range(k + 1, s):
            pj = (P[j * (j + 1) // 2 + k] + sign * sk * x[j]) / c
            P[j * (j + 1) // 2 + k] = pj
            x[j] = c * x[j] - sk * pj
    return P


@partial(jax.jit, static_argnames=("s",))
def cholupdate_packed_jax(P: Array, x: Array, s: int, sign=1.0) -> Array:
    """``cholupdate_packed_numpy`` jitted: the same sweep over the same
    packed 1-D array.  Column k of C is a strided packed read (as in
    Algorithm 4's inner loop), masked to rows >= k."""
    ar = jnp.arange(s)
    col_starts = ar * (ar + 1) // 2  # start of each packed row

    def rot_k(k, carry):
        P, x = carry
        colk = P[col_starts + k]  # C[:, k], valid where ar >= k
        dk = colk[k]
        xk = x[k]
        r, c, sk, _ = _guarded_rotation(dk, xk, sign)
        new = (colk + sign * sk * x) / c
        new = jnp.where(ar > k, new, colk).at[k].set(r)
        x = jnp.where(ar > k, c * x - sk * new, x)
        P = P.at[col_starts + k].set(jnp.where(ar >= k, new, colk))
        return P, x

    P, _ = jax.lax.fori_loop(0, s, rot_k, (P, x))
    return P


def _cholupdate_dense_flagged(L: Array, x: Array, sign) -> Tuple[Array, Array]:
    """One rotation sweep over a dense lower factor (vectorized columns).

    Returns (L', bad): ``bad`` is True iff any rotation hit the downdate
    guard (and was skipped - see the section comment)."""
    n = L.shape[0]
    ridx = jnp.arange(n)

    def rot_k(k, carry):
        L, x, bad_any = carry
        dk = L[k, k]
        xk = x[k]
        r, c, sk, bad = _guarded_rotation(dk, xk, sign)
        col = (L[:, k] + sign * sk * x) / c
        col = jnp.where(ridx > k, col, L[:, k]).at[k].set(r)
        L = L.at[:, k].set(col)
        x = jnp.where(ridx > k, c * x - sk * col, x)
        return L, x, bad_any | bad

    L, _, bad = jax.lax.fori_loop(
        0, n, rot_k, (L, x, jnp.zeros((), jnp.bool_))
    )
    return L, bad


def _cholupdate_dense(L: Array, x: Array, sign) -> Array:
    return _cholupdate_dense_flagged(L, x, sign)[0]


@jax.jit
def cholupdate_dense(L: Array, x: Array, sign=1.0) -> Array:
    """Rank-1 update/downdate of a dense lower factor: L (s, s), x (s,).

    Indefinite downdate rotations are clamp-skipped (finite result, no
    NaNs) - use ``cholupdate_dense_guarded`` when the caller needs to know.
    """
    return _cholupdate_dense(L, x, jnp.asarray(sign, L.dtype))


@jax.jit
def cholupdate_dense_guarded(L: Array, x: Array, sign=1.0) -> Tuple[Array, Array]:
    """``cholupdate_dense`` + guard flag: returns (L', ok).

    ``ok`` is False iff a rotation was guard-skipped (the downdate would
    have driven the diagonal non-positive); the returned factor is then
    still finite, triangular and positive-diagonal, but no longer factors
    ``B + sign * x x^T`` - re-factorize from the statistics.
    """
    L, bad = _cholupdate_dense_flagged(L, x, jnp.asarray(sign, L.dtype))
    return L, ~bad


@jax.jit
def cholupdate_dense_batched(L: Array, x: Array, sign=1.0) -> Array:
    """Member/slot-axis rank-1 update: L (K, s, s), x (K, s)."""
    sg = jnp.asarray(sign, L.dtype)
    return jax.vmap(lambda l, v: _cholupdate_dense(l, v, sg))(L, x)


def cholupdate_window(L: Array, X: Array, sign=1.0) -> Array:
    """Fold a window of samples into the factor: X (W, s), rows applied in
    stream order.  A zero row is an exact no-op (r = |d|, c = 1, sk = 0), so
    callers gate dead/tail samples by scaling rows to zero - the same 0/1
    weight discipline as ``repro.core.online.online_step``."""
    sg = jnp.asarray(sign, L.dtype)

    def fold(t, L):
        return _cholupdate_dense(L, X[t], sg)

    return jax.lax.fori_loop(0, X.shape[0], fold, L)


def _cholupdate_dense_t_flagged(U: Array, x: Array, sign) -> Tuple[Array, Array]:
    """The rotation sweep on the *transposed* factor U = L^T.

    Column k of L is row k of U - a contiguous read/write in row-major
    storage.  The strided column access of the untransposed sweep wastes a
    full cache line per element on CPU (and lane shuffles on TPU), which is
    why the in-state factor (``RidgeState.Lt``) is stored transposed: the
    vmapped per-slot sweep runs ~2x faster than the column form at the
    server's (S, s, s) shapes.  Bit-identical to
    ``cholupdate_dense(U.T, x).T``.  Returns (U', bad) - see
    ``_cholupdate_dense_flagged``.
    """
    n = U.shape[0]
    cidx = jnp.arange(n)

    # The sweep touches exactly one factor row per rotation step (row k is
    # read, rotated, written; every other row is untouched), so it is a
    # ``lax.scan`` over the stacked rows with only (x, bad) in the carry -
    # each output row is written ONCE into the stacked ys.  The equivalent
    # ``fori_loop`` carrying the whole factor forces XLA:CPU to copy the
    # full (.., s, s) buffer every iteration when it cannot prove aliasing
    # (under vmap at the stream server's (S, s, s) shapes that copy was
    # ~95% of the serving step).  Identical arithmetic per element, so the
    # scan is bit-for-bit the loop it replaces.
    def rot_k(carry, inp):
        x, bad_any = carry
        k, rowk = inp
        dk = rowk[k]
        xk = x[k]
        r, c, sk, bad = _guarded_rotation(dk, xk, sign)
        new = (rowk + sign * sk * x) / c
        new = jnp.where(cidx > k, new, rowk).at[k].set(r)
        x = jnp.where(cidx > k, c * x - sk * new, x)
        return (x, bad_any | bad), new

    (_, bad), U = jax.lax.scan(
        rot_k, (x, jnp.zeros((), jnp.bool_)), (cidx, U)
    )
    return U, bad


def _cholupdate_dense_t(U: Array, x: Array, sign) -> Array:
    return _cholupdate_dense_t_flagged(U, x, sign)[0]


@jax.jit
def cholupdate_dense_t(U: Array, x: Array, sign=1.0) -> Array:
    """Rank-1 update/downdate of a transposed factor: U = L^T (s, s)."""
    return _cholupdate_dense_t(U, x, jnp.asarray(sign, U.dtype))


@jax.jit
def cholupdate_dense_t_guarded(U: Array, x: Array, sign=1.0) -> Tuple[Array, Array]:
    """``cholupdate_dense_guarded`` on the transposed factor: (U', ok)."""
    U, bad = _cholupdate_dense_t_flagged(U, x, jnp.asarray(sign, U.dtype))
    return U, ~bad


def cholupdate_window_t(U: Array, X: Array, sign=1.0) -> Array:
    """``cholupdate_window`` on the transposed in-state factor."""
    sg = jnp.asarray(sign, U.dtype)

    def fold(t, U):
        return _cholupdate_dense_t(U, X[t], sg)

    return jax.lax.fori_loop(0, X.shape[0], fold, U)


def cholupdate_window_t_decay(
    U: Array, X: Array, scale: Array, sign=1.0
) -> Array:
    """``cholupdate_window_t`` with a per-row pre-scaling of the factor.

    Before rotating row t of X into U, the whole factor is scaled by
    ``scale[t]`` - the forgetting-factor hook: with scale[t] = sqrt(lambda)
    for live rows (and exactly 1.0 for dead/gated rows, an exact bitwise
    no-op), the maintained system decays as  L L^T <- lambda L L^T + x x^T
    per retained sample, which is exact because scaling commutes with the
    rank-1 rotation.  ``scale = ones`` reduces to ``cholupdate_window_t``
    bit-for-bit (multiplication by 1.0 is the identity).
    """
    sg = jnp.asarray(sign, U.dtype)

    def fold(t, U):
        return _cholupdate_dense_t(U * scale[t], X[t], sg)

    return jax.lax.fori_loop(0, X.shape[0], fold, U)


@jax.jit
def ridge_solve_from_factor(A: Array, L: Array) -> Array:
    """Refresh from a live factor: W~ = A (L L^T)^{-1}, two triangular
    substitutions (Algorithms 3/4), O(s^2 Ny) - no factorization."""
    D = jax.scipy.linalg.solve_triangular(L, A.T, lower=True).T
    return jax.scipy.linalg.solve_triangular(L.T, D.T, lower=False).T


@jax.jit
def ridge_solve_from_factor_t(A: Array, U: Array) -> Array:
    """``ridge_solve_from_factor`` on the transposed factor U = L^T:
    U^T Y = A^T forward, then U W~^T = Y backward (LAPACK handles the
    transpose by flag, no copy)."""
    Y = jax.scipy.linalg.solve_triangular(U, A.T, lower=False, trans="T")
    return jax.scipy.linalg.solve_triangular(U, Y, lower=False).T


@jax.jit
def ridge_solve_from_factor_batched(A: Array, L: Array) -> Array:
    """Batched refresh from live factors: A (K, Ny, s), L (K, s, s)."""
    X = jax.scipy.linalg.cho_solve((L, True), jnp.swapaxes(A, -1, -2))
    return jnp.swapaxes(X, -1, -2)


@partial(jax.jit, static_argnames=("block",))
def ridge_solve_from_factor_t_batched(
    A: Array, U: Array, block: int = 16
) -> Array:
    """Batched refresh from transposed live factors by *blocked
    substitution*:  A (K, Ny, s), U (K, s, s) with U = L^T.

    XLA:CPU lowers the batched triangular-solve primitive poorly (worse
    than the batched factorization it should undercut - the same lowering
    gap PR 1 found for vmapped TRSMs), so the two substitutions run as
    explicit row-block sweeps: per block, an unrolled in-block solve plus
    one batched GEMM for the trailing update.  O(s^2 Ny) per member, ~4x
    faster than ``cho_solve`` at the stream server's (S, s, s) shapes.

    The system pads to a block multiple with an identity diagonal (padded
    rows solve to zero exactly, as in ``repro.kernels.ridge_solve``).
    """
    k, ny, s = A.shape
    pad = (-s) % block
    if pad:
        U = pad_factor_identity(U, pad)
        A = jnp.pad(A, ((0, 0), (0, 0), (0, pad)))
    n = s + pad
    nb = n // block
    ridx = jnp.arange(n)

    # forward:  U^T Y = A^T  (U^T is lower; row block j of U^T is the
    # column block j of U, read as rows of U - contiguous)
    Y = jnp.swapaxes(A, -1, -2)  # (K, n, Ny)

    def fwd(j, Y):
        j0 = j * block
        cols = jax.lax.dynamic_slice(U, (0, 0, j0), (k, n, block))
        done = jnp.where(ridx[None, :, None] < j0, Y, 0.0)
        rhs = jax.lax.dynamic_slice(Y, (0, j0, 0), (k, block, ny))
        rhs = rhs - jnp.einsum("ksb,ksn->kbn", cols, done,
                               precision=DOT_PRECISION)
        Tb = jax.lax.dynamic_slice(cols, (0, j0, 0), (k, block, block))
        sol = jnp.zeros_like(rhs)
        for i in range(block):  # unrolled in-block forward substitution
            v = (rhs[:, i, :] - jnp.einsum("kb,kbn->kn", Tb[:, :, i], sol,
                                             precision=DOT_PRECISION))
            sol = sol.at[:, i, :].set(v / Tb[:, i, i][:, None])
        return jax.lax.dynamic_update_slice(Y, sol, (0, j0, 0))

    Y = jax.lax.fori_loop(0, nb, fwd, Y)

    # backward:  U W~^T = Y  (U upper; high row blocks first)
    Wt = Y

    def bwd(t, Wt):
        j0 = (nb - 1 - t) * block
        rows = jax.lax.dynamic_slice(U, (0, j0, 0), (k, block, n))
        solved = jnp.where(ridx[None, :, None] >= j0 + block, Wt, 0.0)
        rhs = jax.lax.dynamic_slice(Y, (0, j0, 0), (k, block, ny))
        rhs = rhs - jnp.einsum("kbs,ksn->kbn", rows, solved,
                               precision=DOT_PRECISION)
        Tb = jax.lax.dynamic_slice(rows, (0, 0, j0), (k, block, block))
        sol = jnp.zeros_like(rhs)
        for i in range(block - 1, -1, -1):  # unrolled backward substitution
            v = (rhs[:, i, :] - jnp.einsum("kb,kbn->kn", Tb[:, i, :], sol,
                                             precision=DOT_PRECISION))
            sol = sol.at[:, i, :].set(v / Tb[:, i, i][:, None])
        return jax.lax.dynamic_update_slice(Wt, sol, (0, j0, 0))

    Wt = jax.lax.fori_loop(0, nb, bwd, Wt)
    return jnp.swapaxes(Wt, -1, -2)[:, :, :s]


# ---------------------------------------------------------------------------
# Streaming sufficient statistics (paper Eq. 21-22, 38).
# ---------------------------------------------------------------------------


def accumulate_ab(A: Array, B: Array, r_tilde: Array, onehot: Array) -> Tuple[Array, Array]:
    """Rank-k update of (A, B) with a batch of samples.

    r_tilde: (batch, s), onehot: (batch, Ny).
    """
    A = A + jnp.einsum("bc,bs->cs", onehot, r_tilde, precision=DOT_PRECISION)
    B = B + jnp.einsum("bs,bt->st", r_tilde, r_tilde, precision=DOT_PRECISION)
    return A, B


def regularize(B: Array, beta: Array) -> Array:
    """B + beta I, broadcasting over any leading (population) axes."""
    return B + beta * jnp.eye(B.shape[-1], dtype=B.dtype)


# ---------------------------------------------------------------------------
# Table 2 / Table 3 formulas (for the benchmark harness).
# ---------------------------------------------------------------------------


def memory_words_naive(s: int, n_y: int) -> int:
    """Table 2 'naive': B + B^{-1} + A + W~ + buf = 2s(s+Ny) + 1 words."""
    return 2 * s * (s + n_y) + 1


def memory_words_proposed(s: int, n_y: int) -> int:
    """Table 2 'proposed': P + Q = s(s+2Ny)/2 + s/2 words."""
    return (s * (s + 2 * n_y) + s) // 2


def op_counts_naive(s: int, n_y: int) -> dict:
    """Table 3 'naive' (Gauss-Jordan) arithmetic op counts.

    add: 2s^2(s + Ny/2) - 2s^2 = s^2(2s + Ny) - 2s^2;  mul: s^2(2s + Ny).
    """
    return {
        "add": float(s * s * (2 * s + n_y) - 2 * s * s),
        "mul": float(s * s * (2 * s + n_y)),
        "div": float(s),
        "sqrt": 0.0,
    }


def op_counts_proposed(s: int, n_y: int) -> dict:
    """Table 3 'proposed' (1-D Cholesky) arithmetic op counts."""
    return {
        "add": s * s * (s + n_y) / 6 - s / 6 - s * n_y,
        "mul": s * s * (s + n_y) / 6 + s * s / 2 - 2 * s / 3 - s * n_y,
        "div": float(s + 2 * s * n_y),
        "sqrt": float(s),
    }


def count_ops_packed(s: int, n_y: int) -> dict:
    """Exact op count of Algorithms 2+3+4 by loop enumeration (used to
    cross-check the Table 3 closed forms in the benchmark)."""
    add = mul = div = sqrt = 0
    for i in range(s):
        add += i            # diagonal update subs
        mul += i            # squares
        sqrt += 1
        div += 1            # buf = 1/diag  (paper counts the reciprocal)
        for j in range(i + 1, s):
            add += i
            mul += i + 1    # dots + final *buf
    # Alg 3: for each of Ny rows: sum_j (j subs + j muls + 1 div)
    add += n_y * (s * (s - 1) // 2)
    mul += n_y * (s * (s - 1) // 2)
    div += n_y * s
    # Alg 4: mirror of Alg 3
    add += n_y * (s * (s - 1) // 2)
    mul += n_y * (s * (s - 1) // 2)
    div += n_y * s
    return {"add": add, "mul": mul, "div": div, "sqrt": sqrt}
