"""Pallas TPU kernels for the method's compute hot-spot (fused Block-CSR
Laplacian matvec + Chebyshev recurrence), with jnp oracles in ref.py."""

from repro.kernels.autotune import Tiling, select_tiling
from repro.kernels.cheb_bsr import cheb_step_pallas, cheb_union_pallas
from repro.kernels.ops import (
    BlockCsr,
    bsr_from_dense,
    cheb_apply_bsr,
    cheb_apply_bsr_fused,
)

__all__ = [
    "BlockCsr",
    "Tiling",
    "bsr_from_dense",
    "cheb_apply_bsr",
    "cheb_apply_bsr_fused",
    "cheb_step_pallas",
    "cheb_union_pallas",
    "select_tiling",
]
