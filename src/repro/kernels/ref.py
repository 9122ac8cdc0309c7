"""Pure-jnp oracles for the Pallas kernels.

The reference implementations operate on the same Block-ELL operands as the
kernels so the tests compare like-for-like (including padding slots).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["BlockEll", "tile_mask", "bsr_from_dense", "bsr_to_dense",
           "bsr_matvec_ref", "cheb_step_ref", "cheb_apply_bsr_ref"]


@dataclasses.dataclass(frozen=True)
class BlockEll:
    """Block-ELL sparse matrix: fixed number of blocks per block-row.

    TPU adaptation of CSR (DESIGN.md Sec. 3): vertices are spatially
    ordered so nonzeros cluster into few dense tiles per row; each tile is
    an MXU-shaped (block, block) dense matrix. Padding slots have
    ``cols == 0`` and all-zero blocks, so they contribute nothing.

    Attributes:
      blocks: (n_rows, k_max, block, block) dense tiles.
      cols:   (n_rows, k_max) int32 block-column indices.
    """

    blocks: jax.Array
    cols: jax.Array

    @property
    def n_block_rows(self) -> int:
        return self.blocks.shape[0]

    @property
    def k_max(self) -> int:
        return self.blocks.shape[1]

    @property
    def block_size(self) -> int:
        return self.blocks.shape[-1]

    @property
    def n(self) -> int:
        return self.n_block_rows * self.block_size

    @property
    def nnz_blocks(self) -> int:
        """True (non-padding) block count."""
        nz = np.asarray(
            jnp.any(self.blocks != 0.0, axis=(-1, -2)))
        return int(nz.sum())

    @property
    def density(self) -> float:
        return self.nnz_blocks / (self.n_block_rows**2)


def tile_mask(mat, block_size: int) -> np.ndarray:
    """(nb, nb) bool: which ``(block_size x block_size)`` tiles of the
    (N, N) matrix, zero-padded to a multiple of ``block_size``, hold a
    nonzero. One pass over the matrix."""
    nz = np.asarray(mat) != 0
    n = nz.shape[0]
    nb = -(-n // block_size)
    pad = nb * block_size - n
    if pad:
        nz = np.pad(nz, ((0, pad), (0, pad)))
    # Rows first, over contiguous memory; then the short column groups.
    rows = nz.reshape(nb, block_size, nb * block_size).any(axis=1)
    return rows.reshape(nb, nb, block_size).any(axis=2)


def bsr_from_dense(mat, block_size: int, dtype=jnp.float32, *,
                   mask: np.ndarray | None = None) -> BlockEll:
    """Convert a dense (N, N) matrix to Block-ELL (host-side, build time).

    N is zero-padded up to a multiple of ``block_size``. ``k_max`` is the
    max number of nonzero tiles in any block-row (>= 1). ``mask`` is
    ``tile_mask(mat, block_size)`` where the caller has it already; the
    matrix is then read only at its nonzero tiles, which are gathered in
    float64 whatever the matrix's dtype.
    """
    m = np.asarray(mat)
    if mask is None:
        mask = tile_mask(m, block_size)
    nb = mask.shape[0]
    pad = nb * block_size - m.shape[0]
    if pad:
        m = np.pad(m, ((0, pad), (0, pad)))
    tiles = m.reshape(nb, block_size, nb, block_size)
    per_row = mask.sum(axis=1)
    k_max = max(int(per_row.max()), 1)
    rows, js = np.nonzero(mask)  # row-major: each row's tiles in column order
    slots = np.arange(len(rows)) - np.repeat(np.cumsum(per_row) - per_row, per_row)
    blocks = np.zeros((nb, k_max, block_size, block_size))
    cols = np.zeros((nb, k_max), dtype=np.int32)
    blocks[rows, slots] = tiles[rows, :, js, :]
    cols[rows, slots] = js
    return BlockEll(jnp.asarray(blocks, dtype), jnp.asarray(cols))


def bsr_to_dense(bell: BlockEll) -> jax.Array:
    """Densify (oracle / debugging)."""
    nb, k_max, b, _ = bell.blocks.shape
    out = jnp.zeros((nb, nb, b, b), bell.blocks.dtype)
    rows = jnp.repeat(jnp.arange(nb), k_max)
    cols = bell.cols.reshape(-1)
    out = out.at[rows, cols].add(bell.blocks.reshape(nb * k_max, b, b))
    return out.transpose(0, 2, 1, 3).reshape(nb * b, nb * b)


def bsr_matvec_ref(bell: BlockEll, x: jax.Array) -> jax.Array:
    """Oracle ``L @ x`` from Block-ELL operands. x: (N, F)."""
    nb, k_max, b, _ = bell.blocks.shape
    xb = x.reshape(nb, b, -1)
    gathered = xb[bell.cols]  # (nb, k_max, b, F)
    out = jnp.einsum("rkij,rkjf->rif", bell.blocks, gathered,
                     preferred_element_type=jnp.float32)
    return out.reshape(x.shape).astype(x.dtype)


def cheb_step_ref(
    bell: BlockEll,
    t1: jax.Array,
    t2: jax.Array,
    alpha: float,
    *,
    first: bool = False,
) -> jax.Array:
    """Oracle for one fused Chebyshev recurrence step (paper eq. 9).

    first=False: ``T_k  = (2/a) L t1 - 2 t1 - t2``
    first=True:  ``T_1  = (1/a) L t1 - t1``  (t2 ignored)

    Matches the kernel's numerics: f32 accumulate + f32 combine, one final
    cast to the input dtype.
    """
    nb, k_max, b, _ = bell.blocks.shape
    xb = t1.reshape(nb, b, -1)
    lv = jnp.einsum("rkij,rkjf->rif", bell.blocks, xb[bell.cols],
                    preferred_element_type=jnp.float32).reshape(t1.shape)
    t1f = t1.astype(jnp.float32)
    if first:
        out = lv / alpha - t1f
    else:
        out = (2.0 / alpha) * lv - 2.0 * t1f - t2.astype(jnp.float32)
    return out.astype(t1.dtype)


def cheb_apply_bsr_ref(bell, f, coeffs, lmax):
    """Oracle for the full union apply on Block-ELL operands."""
    from repro.core import chebyshev

    return chebyshev.cheb_apply(
        lambda v: bsr_matvec_ref(bell, v), f, coeffs, lmax)
