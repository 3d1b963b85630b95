"""Operations and bytes of the population search, counted from shapes
(the conventions of ``harness.costs``: useful work only, real time steps,
a multiply-add is two operations, bytes the least a kernel must move)."""
from __future__ import annotations

from harness import costs


def train_kernel(lengths, members: int, nx: int) -> tuple:
    """(ops, bytes) of one call of the training kernel
    (``kernels/train.py``: the fused reservoir -> DPRR forward) over
    samples of the given real lengths, vmapped over ``members`` that share
    the masked inputs: each member's recurrence and DPRR over every real
    time step; the inputs read once, and per member-sample the features r
    (Nx (Nx + 1)) and the boundary rows x(T), x(T-1), j(T) written once.
    The closed-form VJP runs after the kernel, outside it."""
    total_t = sum(int(t) for t in lengths)
    n = len(lengths)
    ops = members * total_t * costs.recurrence_step_ops(nx)
    nbytes = costs.F32_BYTES * (total_t * nx + n
                                + members * n * (nx * (nx + 1) + 3 * nx))
    return ops, nbytes


def mask_ops(total_t: int, n_in: int, nx: int) -> int:
    """j(k) = M u(k) over ``total_t`` time steps (shared by the members)."""
    return 2 * total_t * n_in * nx


def ridge_sweep_ops(n_train: int, n_eval: int, s: int, ny: int,
                    n_beta: int) -> float:
    """One member's dual-form ridge over the beta sweep: the Gram R~ R~^T
    (B x B), and per beta its Cholesky factor, the two triangular solves
    for the Ny targets, W~ = X^T R~ and the predictions on the eval split."""
    gram = 2.0 * n_train * n_train * s
    per_beta = (costs.cholesky_ops(n_train) + 2.0 * n_train * n_train * ny
                + 2.0 * n_train * ny * s + 2.0 * n_eval * ny * s)
    return gram + n_beta * per_beta


def job(train_len, eval_len, *, members: int, nx: int, ny: int, n_in: int,
        n_beta: int, rounds: int, steps: int, minibatch: int) -> dict:
    """Operations of one search job (grid evaluation, then ``rounds`` of
    refinement and evaluation), each counted once, and the training
    kernel's (ops, bytes) over its calls.  ``train_len``/``eval_len`` are
    the splits' real lengths in the program's order."""
    s = nx * nx + nx + 1
    train_len = [int(t) for t in train_len]
    eval_len = [int(t) for t in eval_len]
    mb = min(minibatch, len(train_len))
    n_sgd = len(train_len) // mb * mb
    batches = [train_len[i:i + mb] for i in range(0, n_sgd, mb)]
    evals = rounds + 1
    k_ops = k_bytes = 0
    calls = [train_len, eval_len] * evals + batches * (rounds * steps)
    for lens in calls:
        o, b = train_kernel(lens, members, nx)
        k_ops += o
        k_bytes += b
    t_passes = sum(sum(lens) for lens in calls)
    refined = rounds * steps * n_sgd
    total = (k_ops + mask_ops(t_passes, n_in, nx)
             + members * refined * (costs.readout_ops(nx, ny)
                                    + costs.truncated_bp_ops(nx, ny))
             + evals * members * ridge_sweep_ops(
                 len(train_len), len(eval_len), s, ny, n_beta))
    return {"ops": float(total), "kernel_ops": float(k_ops),
            "kernel_bytes": float(k_bytes),
            "served": members * (refined
                                 + evals * (len(train_len) + len(eval_len)))}
