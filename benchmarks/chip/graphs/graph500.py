"""The Graph500 benchmark graph (Graph500 specification v3.0, Sec. 3,
"Graph generation"): a Kronecker (R-MAT) graph of 2^scale vertices from
edgefactor * 2^scale edge draws. Each draw picks one quadrant per bit
level with the initiator's probabilities A, B, C, D, as the
specification's generator does, and the vertex labels are then randomly
permuted.

Drawn once from the configuration's ``graph_seed`` with numpy's
generator, not the reference code's, so the graph has Graph500's
statistics and not its bits. Self-loops are dropped and duplicate edges
merged, with unit weights. ``lmax`` is the Anderson--Morley bound.
"""

import jax
import jax.numpy as jnp
import numpy as np

import field


def edges(scale: int, edgefactor: int, initiator, seed: int):
    """(i, j) int64 endpoints of the ``edgefactor * 2^scale`` draws, with
    the labels permuted; self-loops and repeats included."""
    n = 1 << scale
    m = edgefactor * n
    a, b, c = initiator[:3]
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    rng = np.random.default_rng(seed)
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        i_bit = rng.random(m) > ab
        j_bit = rng.random(m) > np.where(i_bit, c_norm, a_norm)
        i += i_bit.astype(np.int64) << bit
        j += j_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[i], perm[j]


def make(config):
    """(None, adjacency (N, N) float32 on the device, lmax, nnz): no
    coordinates; ``nnz`` counts the adjacency's nonzero entries."""
    n = 1 << config["scale"]
    if config["n_vertices"] != n:
        raise ValueError(f"n_vertices {config['n_vertices']} is not 2^scale = {n}")
    i, j = edges(config["scale"], config["edgefactor"], config["initiator"],
                 config["graph_seed"])
    keep = i != j

    @jax.jit
    def build(i, j):
        adj = jnp.zeros((n, n), jnp.float32).at[i, j].set(1.0).at[j, i].set(1.0)
        return adj, field.anderson_morley(adj), jnp.count_nonzero(adj)

    adj, lmax, nnz = build(jnp.asarray(i[keep], jnp.int32), jnp.asarray(j[keep], jnp.int32))
    return None, adj, float(lmax), int(nnz)
