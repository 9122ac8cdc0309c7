"""Pallas TPU kernel for the method's compute hot-spot (Block-CSR
Laplacian matvec fused with the Chebyshev recurrence step), with jnp
oracles in ref.py."""

from repro.kernels.autotune import choose_f_tile
from repro.kernels.cheb_bsr import cheb_step_pallas
from repro.kernels.ops import BlockCsr, bsr_from_dense, cheb_apply_bsr

__all__ = [
    "BlockCsr",
    "bsr_from_dense",
    "cheb_apply_bsr",
    "cheb_step_pallas",
    "choose_f_tile",
]
