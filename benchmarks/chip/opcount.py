"""Operations and bytes of one Chebyshev union apply, from the algorithm.

Counted from (N, nnz, F, M, eta) alone, not from any tile, grid or
layout choice, so the count is the same whatever implements the apply:

* operations: M sparse products L T (2 nnz F each), M recurrence updates
  T_k = (2/a)(L T_{k-1} - a T_{k-1}) - T_{k-2} (4 N F each, the first
  one 2 N F), and the eq. (11) combine, eta (M+1) multiply-adds over
  N x F (2 eta (M+1) N F);
* bytes (float32 values, int32 indices): per order one pass over L in
  a compressed row form (nnz values and column indices, N + 1 row
  offsets), two Krylov vectors read and one written (3 N F); once the
  input read and the eta x N x F output written.

The eq. (11) accumulator is counted as kept on chip, which makes the
byte count the least any implementation moves.
"""

from __future__ import annotations

F32 = 4
I32 = 4


def union_apply_flops(n: int, nnz: int, f: int, m: int, eta: int) -> float:
    spmv = m * 2.0 * nnz * f
    recur = (m - 1) * 4.0 * n * f + 2.0 * n * f
    combine = 2.0 * eta * (m + 1) * n * f
    return spmv + recur + combine


def union_apply_bytes(n: int, nnz: int, f: int, m: int, eta: int) -> float:
    lap = nnz * (F32 + I32) + (n + 1) * I32
    per_order = lap + 3.0 * n * f * F32
    return m * per_order + n * f * F32 + eta * n * f * F32


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time, and which bound sets it."""
    t_compute = flops / peak["flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_memory >= t_compute:
        return t_memory, "memory"
    return t_compute, "compute"
