"""Seeded inputs: open-loop request traces and signal columns.

``make_trace`` is a copy of the program's load generator trace
(Poisson arrivals, hot-spot stream skew, lane mix), kept here so that no
program change can move it. Seeds are any non-negative integers; numpy's
``SeedSequence`` turns them into generator states and JAX keys.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

LANE_NAMES = ("apply", "solve", "frame")


def rng(seed: int, purpose: int) -> np.random.Generator:
    """A numpy generator for one purpose of one seed."""
    return np.random.default_rng(np.random.SeedSequence([seed, purpose]))


def jax_key(seed: int, purpose: int) -> jax.Array:
    words = np.random.SeedSequence([seed, purpose]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


@functools.partial(jax.jit, static_argnums=(2, 3))
def columns(key, index, n: int, f: int):
    """(n, f) standard normal columns of the ``index``-th batch."""
    return jax.random.normal(jax.random.fold_in(key, index), (n, f), jnp.float32)


@dataclasses.dataclass(frozen=True)
class Trace:
    """One deterministic open-loop workload (sorted by arrival time)."""

    t_arrive: np.ndarray  # (R,) float64 seconds, nondecreasing
    stream: np.ndarray  # (R,) int64 stream id in [0, n_streams)
    lane: np.ndarray  # (R,) int8 index into LANE_NAMES
    tenant: np.ndarray  # (R,) int64 admission-control bucket
    signal: np.ndarray  # (R,) int64 index into the signal pool
    n_streams: int
    n_tenants: int
    n_signals: int

    @property
    def n_requests(self) -> int:
        return len(self.t_arrive)


def make_trace(
    n_streams: int,
    seconds: float,
    rate: float,
    *,
    rng: np.random.Generator,
    hot_frac: float = 0.01,
    hot_mass: float = 0.5,
    lane_mix: tuple[float, float, float] = (0.90, 0.08, 0.02),
    n_tenants: int = 8,
    n_signals: int = 64,
) -> Trace:
    """Poisson arrivals with hot-spot stream skew.

    ``hot_frac`` of the stream ids receive ``hot_mass`` of the requests;
    the rest spread uniformly over the cold set. The number of requests is
    ``round(rate * seconds)`` for every seed, so seeds differ in the order
    and spacing of the work and not in its amount.
    """
    if not 0.0 < hot_frac < 1.0:
        raise ValueError(f"hot_frac must be in (0,1), got {hot_frac}")
    n_requests = max(1, int(round(rate * seconds)))
    gaps = rng.exponential(1.0 / rate, n_requests)
    t_arrive = np.cumsum(gaps) * (seconds / gaps.sum())

    n_hot = max(1, int(round(hot_frac * n_streams)))
    is_hot = rng.random(n_requests) < hot_mass
    hot_ids = rng.integers(0, n_hot, n_requests)
    cold_ids = (rng.integers(0, max(n_streams - n_hot, 1), n_requests) + n_hot
                ).clip(max=n_streams - 1)
    stream = np.where(is_hot, hot_ids, cold_ids)

    mix = np.asarray(lane_mix, np.float64)
    lane = rng.choice(len(LANE_NAMES), size=n_requests, p=mix / mix.sum())

    return Trace(
        t_arrive=t_arrive,
        stream=stream.astype(np.int64),
        lane=lane.astype(np.int8),
        tenant=(stream % n_tenants).astype(np.int64),
        signal=rng.integers(0, n_signals, n_requests),
        n_streams=n_streams,
        n_tenants=n_tenants,
        n_signals=n_signals,
    )
