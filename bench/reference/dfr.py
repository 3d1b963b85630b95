"""Plain reference of the modular DFR, written from the paper alone.

arXiv:2504.11970: masking j(k) = M u(k) (Sec. 2.1), the modular reservoir
(Eq. 14) with the ring wrap x(k)_0 = x(k-1)_Nx, the DPRR readout features
(Eq. 27-28), cross-entropy and the truncated backpropagation of Eq. 25-26
and 33-36, and the ridge solution of the readout from the streamed
statistics A = E R~^T, B = R~ R~^T (Eq. 20-22).  Nothing here imports the
program.  Each function takes ``prec``: ``"highest"`` computes every dot
in float32, ``"high"`` computes it as the chip's three-pass bfloat16
product (operands split into a high and a low bfloat16 part, the low-low
term dropped), on any backend.  The second is the benchmark's control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dot(a, b, prec: str, spec: str):
    """einsum at the named precision (see the module docstring)."""
    if prec == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=F32)
    if prec != "high":
        raise ValueError(f"unknown precision {prec!r}")
    a_hi = a.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(F32)).astype(jnp.bfloat16)
    b_hi = b.astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(F32)).astype(jnp.bfloat16)
    e = partial(jnp.einsum, spec, preferred_element_type=F32)
    return e(a_hi, b_lo) + e(a_lo, b_hi) + e(a_hi, b_hi)


def nonlinearity(name: str, alpha: float):
    if name == "linear":
        return lambda z: alpha * z
    if name == "tanh":
        return lambda z: jnp.tanh(alpha * z)
    raise ValueError(f"unknown nonlinearity {name!r}")


def make_mask(mask_seed: int, n_nodes: int, n_in: int) -> jax.Array:
    """M (Nx, n_in): each node reads one random channel with a random sign
    (the 'select' masking), drawn with JAX's threefry from ``mask_seed``."""
    k_sign, k_sel = jax.random.split(jax.random.PRNGKey(mask_seed))
    bits = jax.random.bernoulli(k_sign, 0.5, (n_nodes, n_in))
    signs = jnp.where(bits, 1.0, -1.0).astype(F32)
    sel = jax.random.randint(k_sel, (n_nodes,), 0, n_in)
    return signs * jax.nn.one_hot(sel, n_in, dtype=F32)


def ring(q, nx: int):
    """L[n, i] = q^(n-i) for i <= n, and [q^1 .. q^Nx] (Eq. 14 unrolled
    along the ring: x(k) = L a(k) + q^{1..Nx} x(k-1)_Nx)."""
    n = jnp.arange(nx)
    e = n[:, None] - n[None, :]
    L = jnp.where(e >= 0, q ** jnp.maximum(e, 0).astype(F32), 0.0)
    return L.astype(F32), (q ** jnp.arange(1, nx + 1).astype(F32)).astype(F32)


def reservoir(p, q, j, length, f, prec):
    """States X (B, T, Nx) of Eq. 14 from x(0) = 0, frozen past length."""
    nx = j.shape[-1]
    L, qpow = ring(q, nx)

    def step(x, inp):
        j_k, k = inp
        a = p * f(j_k + x)
        x_new = dot(a, L, prec, "bi,ni->bn") + x[:, -1:] * qpow
        x_new = jnp.where((k < length)[:, None], x_new, x)
        return x_new, x_new

    x0 = jnp.zeros((j.shape[0], nx), F32)
    ks = jnp.arange(j.shape[1])
    _, xs = jax.lax.scan(step, x0, (jnp.swapaxes(j, 0, 1), ks))
    return jnp.swapaxes(xs, 0, 1)


def dprr(X, length, prec):
    """r (B, Nx(Nx+1)): sum_k x(k) x(k-1)^T flattened, then sum_k x(k),
    over k < length, with x(0) = 0 (Eq. 27-28)."""
    live = (jnp.arange(X.shape[1])[None, :] < length[:, None]).astype(F32)
    x1 = X * live[..., None]
    x0 = jnp.pad(X, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    outer = dot(x1, x0, prec, "bki,bkj->bij")
    nx = X.shape[-1]
    return jnp.concatenate([outer.reshape(-1, nx * nx), x1.sum(1)], -1)


def forward(p, q, W, b, mask, u, length, f, prec):
    """(logits, r, x(T), x(T-1), j(T)) of a batch of samples."""
    j = dot(u, mask, prec, "bti,ni->btn")
    X = reservoir(p, q, j, length, f, prec)
    r = dprr(X, length, prec)
    bi = jnp.arange(X.shape[0])
    last = jnp.maximum(length - 1, 0)
    x_last = X[bi, last]
    x_prev = jnp.where((length >= 2)[:, None],
                       X[bi, jnp.maximum(length - 2, 0)], 0.0)
    j_last = j[bi, last]
    logits = dot(r, W, prec, "br,yr->by") + b
    return logits, r, x_last, x_prev, j_last


def truncated_grads(p, q, W, b, aux, onehot, weight, f, prec):
    """Summed truncated gradients (Eq. 25-26, 33-36) over the weighted
    samples; only x(T) carries gradient to (p, q)."""
    logits, r, x_last, x_prev, j_last = aux
    nx = x_last.shape[-1]
    dlog = (jax.nn.softmax(logits, -1) - onehot) * weight[:, None]  # Eq. 25
    gW = dot(dlog, r, prec, "by,br->yr")                            # Eq. 26
    gb = dlog.sum(0)
    dr = dot(dlog, W, prec, "by,yr->br")
    dr_outer = dr[:, :nx * nx].reshape(-1, nx, nx)
    bpv = dot(dr_outer, x_prev, prec, "bnj,bj->bn") + dr[:, nx * nx:]  # Eq. 33
    L, _ = ring(q, nx)
    delta = dot(bpv, L, prec, "bn,nm->bm")       # Eq. 34: reversed ring sum
    gp = jnp.sum(f(j_last + x_prev) * delta)                         # Eq. 35
    shifted = jnp.concatenate([x_prev[:, -1:], x_last[:, :-1]], -1)
    gq = jnp.sum(shifted * delta)                                    # Eq. 36
    return gp, gq, gW, gb


def sgd(p, q, W, b, grads, lr, inv, train):
    """One SGD step with the configuration's guards: the gradients of
    (p, q) and of (W, b) are each clipped to global norm ``grad_clip``,
    and (p, q) are clamped to the search box."""
    gp, gq, gW, gb = (g * inv for g in grads)
    clip = train["grad_clip"]
    s_res = jnp.minimum(1.0, clip / (jnp.sqrt(gp ** 2 + gq ** 2) + 1e-12))
    s_out = jnp.minimum(1.0, clip / (jnp.sqrt(jnp.sum(gW ** 2)
                                              + jnp.sum(gb ** 2)) + 1e-12))
    p_lo, p_hi = (10.0 ** e for e in train["p_range_log10"])
    q_lo, q_hi = (10.0 ** e for e in train["q_range_log10"])
    return (jnp.clip(p - lr * gp * s_res, p_lo, p_hi),
            jnp.clip(q - lr * gq * s_res, q_lo, q_hi),
            W - lr * gW * s_out, b - lr * gb * s_out)


def ridge(A, B, beta, prec):
    """W~ (Ny, s) solving W~ (B + beta I) = A by Cholesky."""
    with jax.default_matmul_precision("highest" if prec == "highest"
                                      else "high"):
        C = jnp.linalg.cholesky(B + beta * jnp.eye(B.shape[-1], dtype=F32))
        Z = jax.scipy.linalg.solve_triangular(C, A.T, lower=True)
        return jax.scipy.linalg.solve_triangular(C.T, Z, lower=False).T


# ---------------------------------------------------------------------------
# The train-while-serve fleet: one stream, window by window
# ---------------------------------------------------------------------------


def fleet_stream_fn(model: dict, serve: dict, train: dict, prec: str):
    """jit(stream -> (logits per served sample, final state)) for one
    stream served window by window from the fresh state.

    Window k (0-based) of a stream is served at the server's global step
    ``admit + k``.  It is predicted from the pre-update parameters; for
    k < phase_steps the truncated gradients take one SGD step at ``lr``
    and nothing accumulates; from k = phase_steps on, (p, q, W, b) stay
    and [r, 1] folds into (A, B).  After the update, a global step that
    is a multiple of ``refresh_every`` re-solves (W, b) by ridge for a
    stream that has accumulated samples.
    """
    f = nonlinearity(model["nonlinearity"], model["alpha"])
    ny, nx = model["n_classes"], model["n_nodes"]
    s = nx * nx + nx + 1
    W_ = serve["window"]
    lr, phase = serve["lr"], serve["phase_steps"]
    every, beta = serve["refresh_every"], serve["beta"]

    @jax.jit
    def run(mask, u, length, label, n, admit):
        # u (K*W, T, n_in), length/label (K*W,), n samples, admit step
        kw = u.shape[0] // W_
        u = u.reshape(kw, W_, *u.shape[1:])
        length = length.reshape(kw, W_)
        label = label.reshape(kw, W_)

        def window(carry, k):
            p, q, W, b, A, B, count = carry
            live = (k * W_ + jnp.arange(W_)) < n
            wgt = live.astype(F32)
            oh = jax.nn.one_hot(label[k], ny, dtype=F32)
            aux = forward(p, q, W, b, mask, u[k], length[k], f, prec)
            active = k * W_ < n

            def adapt(c):
                p, q, W, b, A, B, count = c
                g = truncated_grads(p, q, W, b, aux, oh, wgt, f, prec)
                inv = 1.0 / jnp.maximum(wgt.sum(), 1.0)
                p, q, W, b = sgd(p, q, W, b, g, lr, inv, train)
                return p, q, W, b, A, B, count

            def accumulate(c):
                p, q, W, b, A, B, count = c
                rt = jnp.concatenate([aux[1], jnp.ones((W_, 1), F32)], -1)
                rt = rt * wgt[:, None]
                A = A + dot(oh, rt, prec, "by,bs->ys")
                B = B + dot(rt, rt, prec, "bs,bt->st")
                return p, q, W, b, A, B, count + wgt.sum()

            new = jax.lax.cond(k < phase, adapt, accumulate, carry)

            def refresh(c):
                p, q, W, b, A, B, count = c
                Wt = ridge(A, B, beta, prec)
                return p, q, Wt[:, :-1], Wt[:, -1], A, B, count

            due = ((admit + k) % every == 0) & (k + 1 >= phase) & (new[6] > 0)
            new = jax.lax.cond(due & active, refresh, lambda c: c, new)
            new = jax.tree_util.tree_map(
                lambda a, o: jnp.where(active, a, o), new, carry)
            return new, aux[0]

        init = (jnp.asarray(model["p_init"], F32),
                jnp.asarray(model["q_init"], F32),
                jnp.zeros((ny, nx * (nx + 1)), F32), jnp.zeros((ny,), F32),
                jnp.zeros((ny, s), F32), jnp.zeros((s, s), F32),
                jnp.zeros((), F32))
        final, logits = jax.lax.scan(window, init, jnp.arange(kw))
        return logits.reshape(kw * W_, ny), final

    return run


def to_numpy(tree):
    return jax.tree_util.tree_map(np.asarray, tree)
