"""Operations and bytes the algorithm needs, counted from shapes, and the
chip's peaks.

Counts are of useful work: real (unpadded) time steps, live samples and
refresh-eligible slots only, each operation once however often the
program recomputes it.  A multiply-add counts as two operations.  Bytes
are the least a kernel must move through HBM: its inputs read once and its
outputs written once.
"""
from __future__ import annotations

#: Published peaks per chip, keyed by JAX's ``device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}

F32_BYTES = 4


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def recurrence_step_ops(nx: int) -> int:
    """One reservoir step of one sample, Eq. 14 in ring form, plus its DPRR
    term: a = p f(j + x) (2 Nx), the ring mix L a (2 Nx^2), the ring wrap
    q^{1..Nx} x_Nx added (2 Nx), and x(k) [x(k-1), 1]^T accumulated
    (2 Nx (Nx + 1))."""
    return 2 * nx * nx + 4 * nx + 2 * nx * (nx + 1)


def readout_ops(nx: int, ny: int) -> int:
    return 2 * ny * nx * (nx + 1) + ny


def streaming_kernel(lengths, nx: int, ny: int, n_windows: int) -> tuple:
    """(ops, bytes) of the serving kernel over samples of the given real
    lengths, read in ``n_windows`` slot windows (one readout tile each)."""
    total_t = int(sum(lengths))
    ops = total_t * recurrence_step_ops(nx) + len(lengths) * readout_ops(nx, ny)
    nbytes = F32_BYTES * (total_t * nx                  # masked inputs
                          + len(lengths) * (1 + ny)      # lengths, logits
                          + n_windows * ny * nx * (nx + 1))  # readout tiles
    return ops, nbytes


def truncated_bp_ops(nx: int, ny: int) -> int:
    """Eq. 25-26 and 33-36 for one sample, and its share of the SGD update:
    dL/dlogits, grad W (2 Ny Nr), dL/dr (2 Ny Nr), bpv (2 Nx^2), the
    reversed ring sum (2 Nx^2), grad p and q (4 Nx), the update of
    (p, q, W, b) (2 (Ny Nr + Ny + 2))."""
    nr = nx * (nx + 1)
    return (3 * ny + 4 * ny * nr + 4 * nx * nx + 4 * nx
            + 2 * (ny * nr + ny + 2))


def statistics_ops(s: int, ny: int) -> int:
    """One sample's fold into A (Ny x s) and B (s x s)."""
    return 2 * s * s + 2 * ny * s


def cholesky_ops(n: int) -> float:
    return n ** 3 / 3.0


def ridge_refresh_ops(s: int, ny: int) -> float:
    """Factor B + beta I and solve for Ny right-hand sides."""
    return s + cholesky_ops(s) + 2.0 * s * s * ny


def fleet_sample_ops(t_len: int, nx: int, ny: int, adapt: bool) -> int:
    """One served sample: its forward and readout, then either a truncated
    BP step (adaptation phase) or its fold into (A, B)."""
    s = nx * nx + nx + 1
    ops = t_len * recurrence_step_ops(nx) + readout_ops(nx, ny)
    return ops + (truncated_bp_ops(nx, ny) if adapt else statistics_ops(s, ny))
