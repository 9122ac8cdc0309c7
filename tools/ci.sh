#!/usr/bin/env bash
# CI entry point: install dev deps, lint, run the test suite on CPU, and
# smoke-run the quickstart example so example drift is caught.
#
# All Pallas paths run with interpret=True on the CPU (the bsr backend
# chooses it there and nowhere else), so the whole matrix — including the fused union-combine
# kernel and the multi-device subprocess tests (forced host devices) — is
# exercised on a plain CPU runner. Collection errors fail the run
# (pytest exits non-zero on them; --co smoke-checks first for clarity).
#
# Lanes (CI_LANE env var, default "fast"):
#   fast — PR feedback: -m "not slow" (skips the 8-device subprocess
#          parity tests, ~minutes saved per run).
#   full — main pushes: everything, with per-test timeouts (pytest-timeout,
#          installed from requirements-dev) so one hung subprocess cannot
#          eat the whole job budget.
set -euo pipefail
cd "$(dirname "$0")/.."

LANE="${CI_LANE:-fast}"

# Purge stray __pycache__ noise from the working tree before anything can
# import it (stale bytecode has shadowed real modules before).
find . -name __pycache__ -prune -exec rm -rf {} +

python -m pip install -r requirements-dev.txt

# Lint. Mandatory on CI (requirements-dev installs ruff there); local
# minimal environments without ruff may still run the tests.
#
# `ruff format --check` is a ratchet: it covers the paths below (new
# subsystems land formatted); extend FORMAT_PATHS as older files get
# reformatted rather than formatting the whole tree in one noise commit.
FORMAT_PATHS=(src/repro/stream src/repro/serve src/repro/dynamic
              src/repro/filters src/repro/solvers
              src/repro/train src/repro/runtime
              benchmarks/loadgen.py tools/bench_check.py)
if python -m ruff --version >/dev/null 2>&1; then
  python -m ruff check .
  python -m ruff format --check "${FORMAT_PATHS[@]}"
elif [ -n "${CI:-}" ]; then
  echo "ruff is required on CI but is not installed" >&2
  exit 1
else
  echo "ruff unavailable; skipping lint (local run)" >&2
fi

# Fail fast and loudly on collection errors (the historical failure mode).
python -m pytest --collect-only -q > /dev/null

TIMEOUT_ARGS=()
if python -c "import pytest_timeout" >/dev/null 2>&1; then
  TIMEOUT_ARGS=(--timeout=900 --timeout-method=thread)
fi

case "$LANE" in
  fast)
    python -m pytest -x -q -m "not slow" "${TIMEOUT_ARGS[@]}"
    # Serving-path smoke: the load generator must drive both engines end
    # to end on a small trace (full-size runs live in the perf-gate job).
    PYTHONPATH=src python -m benchmarks.loadgen --streams 200 --seconds 2 \
      --rate 200
    # Decentralized-training smoke: 3 steps of the bucketed-gossip
    # overlap schedule on a forced 8-device mesh (the full parity /
    # convergence suite is the slow lane; this pins compile + step).
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python examples/train_lm.py --preset tiny --steps 3 \
      --grad-sync gossip
    # Churn smoke: a small mobile-sensor scenario streamed with per-frame
    # GraphDeltas must stay exact vs a from-scratch dense refilter on the
    # evolved graph (full-scale numbers live in tab_churn / the perf gate).
    PYTHONPATH=src python - <<'PY'
import numpy as np
from repro.core.chebyshev import cheb_apply_dense
from repro.dynamic import apply_graph_delta, mobile_sensor_scenario
from repro.filters import GraphFilter
from repro.stream import StreamingFilter

sc = mobile_sensor_scenario(96, 6, mobility="convoy", seed=3)
g = sc.graph0
filt = GraphFilter.from_multipliers(
    [lambda x: 1.0 / (1.0 + x)], 8, graph=g, lmax=1.5 * float(g.lmax_bound()))
lane = StreamingFilter(filt, backend="dense", max_delta_frac=0.9)
cur = g
for fr in sc.frames:
    res = lane.push(fr.signal, delta=fr.delta)
    if fr.delta is not None:
        cur = apply_graph_delta(cur, fr.delta)
    c = lane._coeffs if lane._coeffs is not None else np.atleast_2d(np.asarray(filt.coeffs))
    lm = lane._lmax if lane._lmax is not None else filt.lmax
    ref = np.asarray(cheb_apply_dense(
        cur.laplacian(), fr.signal, np.asarray(c, np.float32), lm))
    err = float(np.max(np.abs(lane._out - ref)))
    assert err < 1e-5, (fr.edges_changed, res.mode, err)
print("churn smoke OK:", len(sc.frames), "frames, graph_version", lane.graph_version)
PY
    ;;
  full)
    python -m pytest -x -q "${TIMEOUT_ARGS[@]}"
    ;;
  *)
    echo "unknown CI_LANE=$LANE (use fast|full)" >&2
    exit 2
    ;;
esac

# Example-drift smoke: the README quickstart must keep running as written.
PYTHONPATH=src python examples/quickstart.py
