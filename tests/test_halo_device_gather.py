"""The distributed apply keeps the signal on the device (four CPU devices).

``halo`` and ``allgather`` permute the panel into the partition order and
the result back on the device: the scatter is a take on the panel's device
and one explicit ``device_put`` onto the mesh, the programs all-gather
their result over the mesh, and the gather is a take on the caller's
device. These tests hold each apply to

* the host path it replaced, bit for bit: ``ctx.cheb_apply`` on the
  scattered panel, copied to numpy and un-permuted there;
* the placement the caller had: one array on the panel's device;
* no host staging: a warm apply of a device-resident panel runs inside
  ``jax.transfer_guard("disallow")``;
* no recompile on a warm apply.

One subprocess (four forced host devices) runs every case and prints a
JSON line; the tests read it.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

CASES = ("halo_overlap", "halo_serial", "allgather", "halo_two_shift")

SCRIPT = r"""
import json
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding
from repro import obs
from repro.core import chebyshev, graph, multipliers
from repro.core.distributed import MultiShiftGraphContext
from repro.filters import GraphFilter

g = graph.connected_sensor_graph(jax.random.PRNGKey(4), n=158, sigma=0.12, kappa=0.14)
single = GraphFilter.from_multipliers(
    [multipliers.heat(0.5), multipliers.tikhonov(1.0, 1)], 8, graph=g)

# Time-vertex product: a sensor graph x a path of 6 frames, two shifts.
gs = graph.connected_sensor_graph(jax.random.PRNGKey(7), n=24, sigma=0.45, kappa=0.5)
t = 6
ag = np.asarray(gs.adjacency, np.float64)
at = np.zeros((t, t))
i = np.arange(t - 1)
at[i, i + 1] = at[i + 1, i] = 1.0
coords = np.column_stack([np.repeat(np.asarray(gs.coords), t, axis=0),
                          np.tile(np.arange(t) / t, ag.shape[0])[:, None]])
g1 = graph.SensorGraph(jnp.asarray(np.kron(ag, np.eye(t))), jnp.asarray(coords))
g2 = graph.SensorGraph(jnp.asarray(np.kron(np.eye(ag.shape[0]), at)), jnp.asarray(coords))
lm1, lm2 = float(g1.lmax_bound()), float(g2.lmax_bound())
joint = chebyshev.separable_joint_coefficients([
    chebyshev.cheb_coefficients([multipliers.heat(0.6)], 5, lm1),
    chebyshev.cheb_coefficients([multipliers.heat(1.2)], 3, lm2)])
two_shift = GraphFilter.from_shifts([g1, g2], joint, lmaxes=[lm1, lm2])

CASES = {
    "halo_overlap": (single, "halo", {"overlap": True}),
    "halo_serial": (single, "halo", {"overlap": False}),
    "allgather": (single, "allgather", {}),
    "halo_two_shift": (two_shift, "halo", {}),
}


def host_apply(ctx, filt, backend, opts, f):
    # The host path: the result copied to numpy and un-permuted there.
    inv = np.empty(ctx.plan.n, np.int64)
    inv[ctx.plan.order] = np.arange(ctx.plan.n)
    fs = ctx.scatter_signal(f)
    if isinstance(ctx, MultiShiftGraphContext):
        out = ctx.cheb_apply_joint(fs, filt.coeffs)
    else:
        out = ctx.cheb_apply(fs, filt.coeffs, filt.lmax, backend=backend,
                             overlap=opts.get("overlap", True))
    return np.asarray(out)[..., inv, :], inv


def host_adjoint(ctx, filt, a, inv):
    a_sh = ctx.scatter_signal(a)
    if isinstance(ctx, MultiShiftGraphContext):
        out = ctx.cheb_adjoint_joint(a_sh, filt.coeffs)
    else:
        out = ctx.cheb_adjoint(a_sh, filt.coeffs, filt.lmax)
    return np.asarray(out)[..., inv, :]


report = {"devices": len(jax.devices())}
for name, (filt, backend, opts) in CASES.items():
    n = filt.graph.n_vertices
    opts = dict(opts, n_parts=4)
    f = jax.random.normal(jax.random.PRNGKey(5), (n, 3))
    ctx = filt.prepare_backend(backend, **opts)
    got = filt.apply(f, backend=backend, **opts)
    want, inv = host_apply(ctx, filt, backend, opts, f)
    adj = filt.adjoint(got, backend=backend, **opts)
    adj_want = host_adjoint(ctx, filt, got, inv)
    # A panel committed to another device of the mesh comes back there.
    other = jax.devices()[2]
    got_other = filt.apply(jax.device_put(f, other), backend=backend, **opts)
    # Warm: the same shapes again, with every transfer that is not an
    # explicit device_put refused.
    f2 = jax.random.normal(jax.random.PRNGKey(6), (n, 3))
    before = obs.compile_counts("compile")
    guard = None
    try:
        with jax.transfer_guard("disallow"):
            warm = filt.apply(f2, backend=backend, **opts)
            warm.block_until_ready()
    except Exception as exc:  # report it, the test names the case
        guard = f"{type(exc).__name__}: {exc}"[:500]
    after = obs.compile_counts("compile")
    warm_want, _ = host_apply(ctx, filt, backend, opts, f2)
    report[name] = {
        "bitwise": bool(np.array_equal(np.asarray(got), want)),
        "warm_bitwise": guard is None and bool(np.array_equal(np.asarray(warm), warm_want)),
        "adjoint_bitwise": bool(np.array_equal(np.asarray(adj), adj_want)),
        "shape": [list(got.shape), list(adj.shape)],
        "placement": [isinstance(got.sharding, SingleDeviceSharding),
                      got.devices() == f.devices(),
                      isinstance(adj.sharding, SingleDeviceSharding),
                      adj.devices() == f.devices(),
                      got_other.devices() == {other}],
        "guard": guard,
        "compiles": sum(after.values()) - sum(before.values()),
    }
print(json.dumps(report))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


@pytest.mark.parametrize("case", CASES)
def test_apply_matches_host_path_bitwise(report, case):
    assert report[case]["bitwise"]


@pytest.mark.parametrize("case", CASES)
def test_adjoint_matches_host_path_bitwise(report, case):
    r = report[case]
    assert r["adjoint_bitwise"]
    eta_n_f, n_f = r["shape"]
    assert n_f == eta_n_f[1:]


@pytest.mark.parametrize("case", CASES)
def test_result_on_the_panel_device(report, case):
    assert report[case]["placement"] == [True] * 5


@pytest.mark.parametrize("case", CASES)
def test_warm_apply_moves_nothing_through_the_host(report, case):
    assert report[case]["guard"] is None
    assert report[case]["warm_bitwise"]


@pytest.mark.parametrize("case", CASES)
def test_warm_apply_compiles_nothing(report, case):
    assert report[case]["compiles"] == 0
