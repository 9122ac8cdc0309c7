"""Quickstart: the paper's Sec. V-B distributed-denoising experiment,
centralized execution (single device), on the unified ``GraphFilter`` API.

Builds the 500-sensor random geometric network, corrupts the smooth field
``f0(n) = nx^2 + ny^2 - 1`` with N(0, 0.25) noise, and denoises with the
Chebyshev approximation of the Prop. 1 multiplier ``tau / (tau + 2 lambda)``
(tau = r = 1, M = 20). Expected output ~= paper numbers: noisy MSE ~ 0.25,
denoised MSE ~ 0.013. The same filter is then applied through the Pallas
``bsr`` backend to show backend dispatch is a one-argument change.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.apps import smooth_heat, ssl_classify
from repro.core import graph, multipliers
from repro.filters import GraphFilter, available_backends


def main() -> None:
    key = jax.random.PRNGKey(0)
    key, kg, kn = jax.random.split(key, 3)

    g = graph.connected_sensor_graph(kg, n=500)  # sigma=0.074, r=0.075
    print(f"graph: N={g.n_vertices} |E|={g.n_edges}")
    print(f"filter backends: {available_backends()}")

    f0 = g.coords[:, 0] ** 2 + g.coords[:, 1] ** 2 - 1.0
    y = f0 + 0.5 * jax.random.normal(kn, f0.shape)

    # One filter object; backends are an apply-time choice.
    filt = GraphFilter.from_multipliers(
        [multipliers.tikhonov(1.0, 1)], order=20, graph=g)

    fhat = filt.apply(y, backend="dense")[0]
    print(f"noisy    MSE = {jnp.mean((y - f0) ** 2):.4f}   (paper: ~0.250)")
    print(f"denoised MSE = {jnp.mean((fhat - f0) ** 2):.4f}   (paper: ~0.013)")

    # Same filter through the Pallas Block-CSR kernel.
    fhat_bsr = filt.apply(y, backend="bsr")[0]
    err = float(jnp.max(jnp.abs(fhat_bsr - fhat)))
    print(f"bsr backend max |delta| vs dense = {err:.2e}")
    assert err < 1e-4

    lmax = filt.lmax
    smoothed = smooth_heat(g, y, lmax, t=2.0, order=20)
    print(f"heat-smoothed MSE = {jnp.mean((smoothed - f0) ** 2):.4f}")

    # Semi-supervised classification: reveal 10% of sign labels.
    key, km = jax.random.split(key)
    true_label = jnp.where(f0 >= jnp.median(f0), 1.0, -1.0)
    mask = jax.random.uniform(km, f0.shape) < 0.1
    pred = ssl_classify(g, jnp.where(mask, true_label, 0.0), lmax)
    acc = jnp.mean((pred == true_label)[~mask])
    print(f"SSL accuracy on unlabelled nodes = {acc:.3f} "
          f"({int(mask.sum())} labels revealed)")


if __name__ == "__main__":
    main()
