"""The deployment's sensor field and filter bank, made by the benchmark.

Everything here is the yardstick's own copy, independent of ``repro``:

* the field recipe of the paper's sensor-network experiment (Shuman,
  Vandergheynst & Frossard 2011, arXiv:1105.1891, Sec. V-B): sensors
  uniform in the unit square, thresholded Gaussian weights of eq. (1).
  kappa keeps the paper's connectivity margin at any N: at N=500 its
  kappa=0.075 gives pi kappa^2 N = 1.42 ln N, and scaling kappa by
  sqrt(500 ln N / (N ln 500)) keeps that ratio; sigma keeps sigma/kappa;
* the Anderson--Morley spectrum bound (Sec. IV-A);
* the SGWT bank of Hammond, Vandergheynst & Gribonval (the paper's
  Sec. V-C operator) and the Chebyshev coefficients of eq. (8) by
  Chebyshev--Gauss quadrature.

The field is built on the device in one jitted call from the
configuration's ``field_seed``; it is the deployment, so every ``--seed``
filters signals on the same field and does the same amount of work.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PAPER_N = 500
PAPER_KAPPA = 0.075
PAPER_SIGMA = 0.074


def field_radii(n: int) -> tuple[float, float]:
    """(kappa, sigma) at ``n`` sensors, keeping the paper's margin."""
    scale = math.sqrt(PAPER_N * math.log(n) / (n * math.log(PAPER_N)))
    return PAPER_KAPPA * scale, PAPER_SIGMA * scale


def make_field(field_seed: int, n: int, kappa: float, sigma: float):
    """(coords (n, 2), adjacency (n, n), lmax, nnz) of the field.

    Coordinates and adjacency stay on the device. ``lmax`` is the
    Anderson--Morley bound max_{i~j} d_i + d_j as a host float: the
    deployment's stated spectrum bound, handed to the program and used by
    the reference alike. ``nnz`` counts the adjacency's nonzero entries.
    """

    @jax.jit
    def build(key):
        coords = jax.random.uniform(key, (n, 2), jnp.float32)
        d2 = jnp.sum((coords[:, None, :] - coords[None, :, :]) ** 2, axis=-1)
        w = jnp.where(d2 <= kappa**2, jnp.exp(-d2 / (2.0 * sigma**2)), 0.0)
        adj = jnp.where(jnp.eye(n, dtype=bool), 0.0, w)
        deg = adj.sum(axis=1)
        lmax = jnp.max(jnp.where(adj > 0, deg[:, None] + deg[None, :], 0.0))
        nnz = jnp.count_nonzero(adj)
        return coords, adj, lmax, nnz

    coords, adj, lmax, nnz = build(jax.random.PRNGKey(field_seed))
    return coords, adj, float(lmax), int(nnz)


def sgwt_bank(lmax: float, n_scales: int, k_ratio: float = 20.0):
    """The SGWT union [h, g(t_1 x), ..., g(t_J x)] (eta = J + 1).

    g: x^2 below 1, the cubic -5 + 11x - 6x^2 + x^3 on [1, 2], (2/x)^2
    above (x1=1, x2=2, alpha=beta=2). h: gamma exp(-(x / (0.6 lmin))^4)
    with lmin = lmax / K and gamma the maximum of g. Scales log-spaced
    from 2/lmin down to 1/lmax.
    """

    def g(x):
        x = np.asarray(x, np.float64)
        return np.where(x < 1.0, x**2,
                        np.where(x <= 2.0, -5.0 + 11.0 * x - 6.0 * x**2 + x**3,
                                 (2.0 / np.maximum(x, 1e-30)) ** 2))

    lmin = lmax / k_ratio
    gamma = float(np.max(g(np.linspace(0.0, lmax, 4096))))
    scales = np.exp(np.linspace(np.log(2.0 / lmin), np.log(1.0 / lmax), n_scales))
    bank = [lambda x: gamma * np.exp(-((np.asarray(x, np.float64) / (0.6 * lmin)) ** 4))]
    bank += [lambda x, t=t: g(t * np.asarray(x, np.float64)) for t in scales]
    return bank


def cheb_coefficients(bank, order: int, lmax: float) -> np.ndarray:
    """Eq. (8): c_jk = (2/pi) int_0^pi cos(k th) g_j(a (cos th + 1)) dth,
    a = lmax / 2, by the midpoint rule on 4 max(M+1, 64) nodes.

    Returns (eta, M+1) float64; c_j0 enters the sum with a factor 1/2.
    """
    p = 4 * max(order + 1, 64)
    theta = np.pi * (np.arange(p) + 0.5) / p
    x = 0.5 * lmax * (np.cos(theta) + 1.0)
    basis = np.cos(np.outer(np.arange(order + 1), theta))
    return np.stack([(2.0 / p) * (basis @ g(x)) for g in bank])
