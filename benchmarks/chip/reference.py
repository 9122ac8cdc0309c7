"""The plain reference: the union apply of eqs. (9)-(11) as a dense jnp
recurrence, and the control that computes it one precision lower.

The reference builds its own Laplacian L = D - A from the benchmark's
adjacency and takes its own coefficients (``field.cheb_coefficients``),
so it shares nothing with the program's backends.

Precision. The configurations state float32 at matmul precision
``highest``. The reference multiplies at ``highest``. The control is the
same recurrence with each product in the nearest precision below, three
bfloat16 passes (what ``high`` does on a TPU): every operand is split
into a bfloat16 head and a bfloat16 tail, and the tail-by-tail product is
dropped. The split is explicit bit masking, so the control reads the same
on a CPU and on a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16_head(x):
    """x with its mantissa cut to bfloat16's 8 bits, as float32. Done by
    masking bits: a float32 -> bfloat16 -> float32 round trip may be
    folded away by the compiler (excess precision is allowed), which on a
    TPU turned the three passes into one."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split_bf16(x):
    hi = _bf16_head(x)
    return hi, _bf16_head(x - hi)


def _matmul(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision == "bf16x3":
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        mm = functools.partial(jnp.matmul, precision=HIGHEST)  # exact on bf16 values
        return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("precision",))
def union_apply(lap, f, coeffs, lmax, precision: str = "highest"):
    """(eta, N, F) = [sum_k c_jk Tbar_k(L) f]_j, c_j0 halved (eq. 7)."""
    alpha = lmax / 2.0
    t0 = f
    t1 = (_matmul(lap, f, precision) - alpha * f) / alpha
    acc = 0.5 * coeffs[:, 0, None, None] * t0 + coeffs[:, 1, None, None] * t1

    def body(carry, c_k):
        t1, t0, acc = carry
        tk = (2.0 / alpha) * (_matmul(lap, t1, precision) - alpha * t1) - t0
        return (tk, t1, acc + c_k[:, None, None] * tk), None

    (_, _, acc), _ = jax.lax.scan(body, (t1, t0, acc), coeffs[:, 2:].T)
    return acc


@jax.jit
def laplacian(adj):
    return jnp.diag(adj.sum(axis=1)) - adj


def reference_apply(adj, f, coeffs: np.ndarray, lmax: float, *,
                    precision: str = "highest", block: int = 128):
    """Reference (or control) output for the (N, F) inputs ``f``, in
    blocks of ``block`` columns; returns a host float32 array."""
    lap = laplacian(adj)
    c = jnp.asarray(coeffs, jnp.float32)
    lm = jnp.float32(lmax)
    outs = []
    for s in range(0, f.shape[1], block):
        fb = jnp.asarray(f[:, s:s + block], jnp.float32)
        outs.append(np.asarray(union_apply(lap, fb, c, lm, precision=precision)))
    del lap
    return np.concatenate(outs, axis=2)
