"""The Block-CSR Laplacian layout, and pure-jnp oracles for the Pallas
kernels.

The reference implementations operate on the same Block-CSR operands as
the kernels so the tests compare like-for-like (including the zero tiles
that keep empty block-rows written).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["BlockCsr", "tile_mask", "permuted_tile_mask", "stored_tiles", "bsr_from_dense",
           "bsr_to_dense", "bsr_matvec_ref", "cheb_step_ref", "cheb_apply_bsr_ref"]


@dataclasses.dataclass(frozen=True)
class BlockCsr:
    """Block-CSR sparse matrix: the stored tiles as one list, by row.

    TPU adaptation of CSR (DESIGN.md Sec. 3): vertices are ordered so
    nonzeros cluster into few dense tiles; each tile is an MXU-shaped
    (block, block) dense matrix. Tiles are sorted by block-row, then
    block-column. Every block-row stores at least one tile (an all-zero
    diagonal tile where it has none), so a kernel that visits the stored
    tiles writes every row of its output.

    Attributes:
      blocks: (n_tiles, block, block) dense tiles.
      rows:   (n_tiles,) int32 block-row of each tile, nondecreasing.
      cols:   (n_tiles,) int32 block-column of each tile.
    """

    blocks: jax.Array
    rows: jax.Array
    cols: jax.Array

    @property
    def n_tiles(self) -> int:
        return self.blocks.shape[0]

    @property
    def block_size(self) -> int:
        return self.blocks.shape[-1]

    @property
    def n_block_rows(self) -> int:
        return int(self.rows[-1]) + 1

    @property
    def n(self) -> int:
        return self.n_block_rows * self.block_size

    @property
    def tile_bytes(self) -> int:
        """Bytes of tiles one pass over the matrix streams."""
        return self.blocks.size * self.blocks.dtype.itemsize


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


def permuted_tile_mask(mat, perm: np.ndarray, block_size: int) -> np.ndarray:
    """``tile_mask(mat[perm][:, perm], block_size)`` from the nonzeros of
    ``mat`` alone, without the permuted copy."""
    r, c = np.nonzero(np.asarray(mat))
    n = len(perm)
    pos = np.empty(n, dtype=np.int64)
    pos[perm] = np.arange(n)
    nb = -(-n // block_size)
    mask = np.zeros((nb, nb), dtype=bool)
    mask[pos[r] // block_size, pos[c] // block_size] = True
    return mask


def stored_tiles(mask: np.ndarray) -> np.ndarray:
    """The tiles ``bsr_from_dense`` stores for a tile mask: the nonzero
    ones, plus the diagonal tile of every block-row that has none."""
    return mask | np.diag(~mask.any(axis=1))


def bsr_from_dense(mat, block_size: int, dtype=jnp.float32, *,
                   perm: np.ndarray | None = None,
                   mask: np.ndarray | None = None) -> BlockCsr:
    """Convert a dense (N, N) matrix to Block-CSR (host-side, build time).

    The matrix is taken in the vertex order ``perm`` (row and column
    ``i`` of the result are vertex ``perm[i]``; default: as it is) and
    zero-padded up to a multiple of ``block_size``. ``mask`` is the
    permuted matrix's ``tile_mask`` where the caller has it already. The
    matrix is read only at the stored tiles, one block-row at a time, in
    float32: no permuted or padded copy of it is made.
    """
    m = np.asarray(mat)
    n, b = m.shape[0], block_size
    if perm is None:
        perm = np.arange(n)
    if mask is None:
        mask = permuted_tile_mask(m, perm, b)
    rows, cols = np.nonzero(stored_tiles(mask))  # row-major: each row's tiles in column order
    nb = mask.shape[0]
    at = np.full(nb * b, n)  # the vertex at each padded position; n past the end
    at[:n] = perm
    real = at < n
    ptr = np.searchsorted(rows, np.arange(nb + 1))
    blocks = np.zeros((len(rows), b, b), np.float32)
    for r in range(nb):
        lo, hi = ptr[r], ptr[r + 1]
        ri = at[r * b:(r + 1) * b]
        ci = (cols[lo:hi, None] * b + np.arange(b)).ravel()
        stripe = np.zeros((b, (hi - lo) * b), np.float32)
        stripe[np.ix_(real[r * b:(r + 1) * b], real[ci])] = m[np.ix_(ri[ri < n], at[ci][real[ci]])]
        blocks[lo:hi] = stripe.reshape(b, hi - lo, b).transpose(1, 0, 2)
    return BlockCsr(jnp.asarray(blocks, dtype), jnp.asarray(rows, jnp.int32),
                    jnp.asarray(cols, jnp.int32))


def bsr_to_dense(bsr: BlockCsr) -> jax.Array:
    """Densify (oracle / debugging)."""
    nb, b = bsr.n_block_rows, bsr.block_size
    out = jnp.zeros((nb, nb, b, b), bsr.blocks.dtype)
    out = out.at[bsr.rows, bsr.cols].add(bsr.blocks)
    return out.transpose(0, 2, 1, 3).reshape(nb * b, nb * b)


def _tile_products(bsr: BlockCsr, x: jax.Array) -> jax.Array:
    """(N, F) ``L @ x`` in f32: each tile against its gathered column
    block, summed into its block-row."""
    b = bsr.block_size
    nb = x.shape[0] // b
    xb = x.reshape(nb, b, -1)
    prods = jnp.einsum("tij,tjf->tif", bsr.blocks, xb[bsr.cols],
                       preferred_element_type=jnp.float32)
    return jax.ops.segment_sum(prods, bsr.rows, num_segments=nb).reshape(x.shape)


def bsr_matvec_ref(bsr: BlockCsr, x: jax.Array) -> jax.Array:
    """Oracle ``L @ x`` from Block-CSR operands. x: (N, F)."""
    return _tile_products(bsr, x).astype(x.dtype)


def cheb_step_ref(
    bsr: BlockCsr,
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
    lv = _tile_products(bsr, t1)
    t1f = t1.astype(jnp.float32)
    if first:
        out = lv / alpha - t1f
    else:
        out = (2.0 / alpha) * lv - 2.0 * t1f - t2.astype(jnp.float32)
    return out.astype(t1.dtype)


def cheb_apply_bsr_ref(bsr, f, coeffs, lmax):
    """Oracle for the full union apply on Block-CSR operands."""
    from repro.core import chebyshev

    return chebyshev.cheb_apply(
        lambda v: bsr_matvec_ref(bsr, v), f, coeffs, lmax)
