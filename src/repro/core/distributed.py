"""Distributed application of Chebyshev-approximated operators (paper Sec. IV).

Algorithm 1 on a device mesh: vertices are partitioned across devices along
one mesh axis; every Chebyshev order exchanges **only partition-boundary
vertex values** (the halo), the mesh analog of the paper's
"transmit (Tbar_{k-1}(L) f)_n to all neighbours".

Two interchangeable matvec backends:

* ``halo``      — precomputed halo exchange via ``lax.all_to_all``: device p
  sends device q exactly the values of p's vertices that q's rows of L
  touch. Communication per order = ``sum_{p,q} |boundary(p,q)|`` words
  (<= 2|E|: a boundary vertex is sent once per neighbouring *partition*,
  not once per edge — a broadcast saving over the radio model).
* ``allgather`` — naive baseline: all-gather the full signal every order
  (N words/device). This is the §Perf "before" configuration for the
  graph-signal mesh cell.

Both run under ``shard_map`` and compose with ``cheb_apply`` /
``GraphFilter`` unchanged, because those only see a matvec closure.

The halo backend additionally ships an **overlapped schedule**
(:func:`halo_cheb_apply_overlapped`, the default): each partition's rows
are split into a boundary block (rows with at least one off-partition
column — the only vertices other devices ever need) and an interior block
(rows whose columns are all owned locally). Step k computes the boundary
rows of ``T_k`` first, immediately issues the ``all_to_all`` that step
k+1 will consume, and only then computes the interior rows — so the
exchange is in flight while the bulk of the matvec runs, instead of
serializing exchange -> matvec every order (DESIGN.md Sec. 6.4).

The partition plan is built on host (static graph topology — the paper's
nodes likewise know their neighbours up front) and carried as sharded
arrays: stacking the per-device tables over the leading (device) axis.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.compat import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import graph as graph_lib

__all__ = ["PartitionPlan", "build_partition_plan",
           "build_shift_partition_plans", "repair_partition_plan",
           "distributed_cheb_apply",
           "halo_matvec", "halo_cheb_apply_overlapped", "allgather_matvec",
           "DistributedGraphContext", "MultiShiftGraphContext"]


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """Host-built static partition of a graph over ``n_parts`` devices.

    All arrays have a leading device axis of size P and are sharded on it.

    Attributes:
      order: (N_pad,) vertex permutation; device p owns slots
        ``[p*n_local, (p+1)*n_local)`` of the *permuted* vertex order.
        Padding slots (degree-0 dummy vertices) map to index N (clamped).
      l_own: (P, n_local, n_local) diagonal Laplacian blocks (own-own).
      l_halo: (P, n_local, P*max_halo) off-diagonal rows, columns indexed by
        the *received* halo buffer layout (slot q*max_halo + i = i-th value
        received from device q).
      send_idx: (P, P, max_halo) local indices each device sends to each
        other device (padded with 0; receivers only read used columns).
      halo_words: true (unpadded) number of scalar words exchanged per
        matvec across all devices — the paper's message-count analog.
      n_local: vertices per device (padded).
      n: true number of vertices.
      n_boundary: uniform boundary-block size (clamped >= 1): within each
        partition the local rows are ordered boundary-first, so rows
        ``[0, boundary_counts[p])`` are exactly the rows with at least one
        off-partition Laplacian column, and every ``send_idx`` entry lands
        below ``n_boundary``. The overlapped schedule computes this block
        first and issues its exchange before the interior matvec.
      boundary_counts: (P,) true per-partition boundary-row counts
        (``n_boundary`` is their max, padded uniform for shard_map).
      pair_counts: (P, P) used-lane counts — ``pair_counts[p, q]`` is the
        number of vertices p receives from q per matvec (``halo_words`` is
        its sum). Recorded by the builder so incremental plan repair can
        tell used send lanes from zero padding without device->host
        round-trips; ``None`` on plans built before churn support (repair
        then recovers the counts from the halo tables).
    """

    order: np.ndarray
    l_own: jax.Array
    l_halo: jax.Array
    send_idx: jax.Array
    halo_words: int
    n_local: int
    n: int
    n_boundary: int = 1
    boundary_counts: np.ndarray | None = None
    pair_counts: np.ndarray | None = None

    @property
    def n_parts(self) -> int:
        return self.l_own.shape[0]

    def owner_of(self) -> np.ndarray:
        """(N,) partition owning each *original* (unpermuted) vertex."""
        inv = np.empty(self.n, dtype=np.int64)
        inv[self.order[: self.n]] = np.arange(self.n)
        return inv // self.n_local

    def vertex_send_counts(self, adjacency) -> np.ndarray:
        """(N,) per-vertex halo fan-out: how many *other* partitions each
        vertex is sent to per matvec in which it is active.

        A boundary vertex is sent once per neighbouring partition (not once
        per edge), so summing this vector over all vertices reproduces
        ``halo_words`` exactly — the delta-support accounting below is the
        same model restricted to the active set.
        """
        a = np.asarray(adjacency) != 0.0
        owner = self.owner_of()
        counts = np.zeros(self.n, dtype=np.int64)
        for p in range(self.n_parts):
            has_nbr_in_p = a[:, owner == p].any(axis=1)
            counts += (has_nbr_in_p & (owner != p)).astype(np.int64)
        return counts

    def delta_halo_words(
        self, adjacency, support, order: int, *, counts=None
    ) -> int:
        """Halo words for one delta apply of a signal supported on ``S``.

        Recurrence step k consumes ``T_{k-1}``, supported on the (k-1)-hop
        neighbourhood of S, so only active boundary vertices are exchanged:
        ``words = sum_{k=0}^{M-1} sum_{v in N_k(S)} send_counts[v]``. With
        full support every term equals ``halo_words`` and the total reduces
        to the dense model ``order * halo_words`` (tested). Pass a
        precomputed ``counts=vertex_send_counts(adjacency)`` when calling
        per frame (the streaming layer caches it once per stream).
        """
        if counts is None:
            counts = self.vertex_send_counts(adjacency)
        mask = np.asarray(support, dtype=bool)
        words = 0
        for k in range(order):
            step_words = int(counts[mask].sum())
            words += step_words
            if mask.all():
                # Saturated: every remaining step costs the full halo.
                words += step_words * (order - 1 - k)
                break
            mask = graph_lib.khop_neighborhood(adjacency, mask, 1)
        return words


def _partition_layout(adjacency, coords, n_parts: int):
    """Spatial order + boundary-first refinement for one edge pattern.

    Returns ``(order, boundary_counts, n_local)`` — the final vertex
    permutation (refinement absorbed), the true per-partition boundary-row
    counts, and the padded per-device slot count. Factored out of
    :func:`build_partition_plan` so multi-shift filters can compute ONE
    layout from the union edge pattern and build every shift's tables
    under it (:func:`build_shift_partition_plans`).
    """
    a = np.asarray(adjacency, dtype=np.float64)
    n = a.shape[0]
    if coords is not None:
        order = graph_lib.spatial_partition_order(np.asarray(coords), n_parts)
    else:
        order = np.arange(n)
    n_pad = ((n + n_parts - 1) // n_parts) * n_parts
    n_local = n_pad // n_parts

    # Permute-and-pad the Laplacian (padding vertices are isolated).
    lap = np.zeros((n_pad, n_pad))
    lp = np.diag(a.sum(axis=1)) - a
    lap[:n, :n] = lp[np.ix_(order, order)]

    # Boundary-first local refinement: within each partition, stably move
    # the rows with any off-partition column ahead of the interior rows.
    # Sent vertices are always boundary rows (L is symmetric: if q's rows
    # touch p's vertex v, then v's row touches q), so after this reorder
    # every send_idx entry indexes into the leading boundary block — the
    # overlapped schedule can exchange T_k as soon as that block is
    # computed. Padding rows are all-zero (interior) and stay at the tail.
    boundary_counts = np.zeros(n_parts, dtype=np.int64)
    local_perm = np.empty(n_pad, dtype=np.int64)
    for p in range(n_parts):
        sl = slice(p * n_local, (p + 1) * n_local)
        rows = lap[sl]
        off_block = np.ones(n_pad, dtype=bool)
        off_block[sl] = False
        is_boundary = np.any(rows[:, off_block] != 0.0, axis=1)
        boundary_counts[p] = int(is_boundary.sum())
        local_perm[sl] = p * n_local + np.concatenate(
            [np.nonzero(is_boundary)[0], np.nonzero(~is_boundary)[0]])
    # Padding rows keep the global tail slots, so real vertices still
    # occupy local_perm[:n] and the public `order` absorbs the refinement.
    assert np.all(local_perm[:n] < n)
    return order[local_perm[:n]], boundary_counts, n_local


def _plan_tables(
    adjacency, order, boundary_counts, n_parts: int, n_local: int, dtype
) -> PartitionPlan:
    """Build a plan's halo tables for ``adjacency`` under a FIXED layout.

    ``order``/``boundary_counts`` come from :func:`_partition_layout` — of
    this adjacency itself (the single-shift path), or of a union edge
    pattern that contains it (the multi-shift path; every shift-boundary
    row is then a union-boundary row, so the boundary-block invariant the
    overlapped schedule relies on still holds, re-asserted below).
    """
    a = np.asarray(adjacency, dtype=np.float64)
    n = a.shape[0]
    n_pad = n_local * n_parts

    lap = np.zeros((n_pad, n_pad))
    lp = np.diag(a.sum(axis=1)) - a
    lap[:n, :n] = lp[np.ix_(order, order)]
    n_boundary = max(1, int(boundary_counts.max()))

    owner = np.repeat(np.arange(n_parts), n_local)

    # For each ordered pair (p, q != p): vertices of q that p's rows touch.
    need: list[list[np.ndarray]] = [[None] * n_parts for _ in range(n_parts)]
    max_halo = 1
    for p in range(n_parts):
        rows = lap[p * n_local : (p + 1) * n_local]
        touched = np.nonzero(np.any(rows != 0.0, axis=0))[0]
        for q in range(n_parts):
            if q == p:
                continue
            t = touched[(owner[touched] == q)]
            need[p][q] = t
            max_halo = max(max_halo, len(t))

    send_idx = np.zeros((n_parts, n_parts, max_halo), dtype=np.int32)
    l_halo = np.zeros((n_parts, n_local, n_parts * max_halo))
    l_own = np.zeros((n_parts, n_local, n_local))
    pair_counts = np.zeros((n_parts, n_parts), dtype=np.int64)
    for p in range(n_parts):
        sl = slice(p * n_local, (p + 1) * n_local)
        l_own[p] = lap[sl, sl]
        for q in range(n_parts):
            if q == p:
                continue
            t = need[p][q]  # global ids owned by q, needed by p
            pair_counts[p, q] = len(t)
            # Sent vertices must sit in q's boundary block (symmetry).
            assert np.all(t - q * n_local < boundary_counts[q]), (p, q)
            # q sends these to p: record in q's send table, destination p.
            send_idx[q, p, : len(t)] = t - q * n_local
            # p's halo columns for data received from q sit at block q.
            l_halo[p][:, q * max_halo : q * max_halo + len(t)] = lap[sl, t]

    return PartitionPlan(
        order=order,
        l_own=jnp.asarray(l_own, dtype),
        l_halo=jnp.asarray(l_halo, dtype),
        send_idx=jnp.asarray(send_idx),
        halo_words=int(pair_counts.sum()),
        n_local=n_local,
        n=n,
        n_boundary=n_boundary,
        boundary_counts=boundary_counts,
        pair_counts=pair_counts,
    )


def build_partition_plan(
    adjacency, coords, n_parts: int, dtype=jnp.float32
) -> PartitionPlan:
    """Partition a graph spatially and precompute halo-exchange tables."""
    with obs.span("halo.plan", n=int(adjacency.shape[0]), n_parts=n_parts):
        order, boundary_counts, n_local = _partition_layout(
            adjacency, coords, n_parts)
        return _plan_tables(
            adjacency, order, boundary_counts, n_parts, n_local, dtype)


def build_shift_partition_plans(
    adjacencies, coords, n_parts: int, dtype=jnp.float32
) -> tuple[PartitionPlan, ...]:
    """Per-shift plans over ONE shared vertex layout (DESIGN.md Sec. 11).

    A multi-shift filter's joint recurrence interleaves matvecs in several
    shift operators over the *same* signal vector, so every shift must see
    the vertices in the same order — one scatter, one gather, R exchange
    plans. The layout (spatial order + boundary-first refinement) is
    computed from the union edge pattern ``sum_r |A_r|``; each shift's halo
    tables are then built under that fixed order. Since every shift's
    edges are a subset of the union's, each shift's sent vertices land
    inside the union boundary block — the overlapped-schedule invariant —
    and each plan carries its own ``halo_words`` (the per-shift words
    model: shift r costs ``count_r * halo_words_r``; a temporal shift
    whose edges never cross the spatial partition cut has
    ``halo_words == 0`` and is communication-free).

    Returns one :class:`PartitionPlan` per adjacency; all share ``order``,
    ``n_local`` and ``boundary_counts``.
    """
    mats = [np.abs(np.asarray(a, dtype=np.float64)) for a in adjacencies]
    if not mats:
        raise ValueError("need at least one adjacency")
    union = mats[0].copy()
    for m in mats[1:]:
        union += m
    order, boundary_counts, n_local = _partition_layout(
        union, coords, n_parts)
    return tuple(
        _plan_tables(a, order, boundary_counts, n_parts, n_local, dtype)
        for a in adjacencies
    )


def repair_partition_plan(
    plan: PartitionPlan, adjacency, touched, dtype=jnp.float32
) -> PartitionPlan:
    """Incrementally patch a plan after a topology delta (DESIGN.md Sec. 10).

    ``touched`` must contain BOTH endpoints of every changed edge (what
    ``GraphDelta.touched`` / ``apply_delta_inplace`` return); ``adjacency``
    is the NEW (N, N) matrix. The vertex->partition assignment is kept, so
    only the *dirty* partitions — owners of touched vertices — need new
    tables. The correctness lemma behind the cheap path: a changed edge
    makes both its endpoints touched, hence both their owners dirty; a
    clean partition therefore kept every incident edge of every vertex it
    owns, so its row values, boundary split, need sets and lane layout are
    all provably unchanged. Per pair:

    * dirty p, dirty q — recompute p's need set from q, q's send lanes and
      p's halo block from fresh rows (as the builder does, locally);
    * dirty p, clean q — p's halo block from q holds the same values in
      the same lanes, only row-permuted by p's new boundary-first order;
      p's send table to q remaps old local indices through the inverse
      permutation (same vertices, new positions — still inside the
      boundary block, asserted); q's tables are byte-identical.

    Shape stability: ``n_boundary`` and ``max_halo`` only ever grow, and
    only when a dirty partition actually needs more rows/lanes — otherwise
    every array keeps its shape and cached shard_map programs serve the
    repaired plan without retracing. Cost is O(|dirty| * n_local * N)
    against the full rebuild's O(N^2) + P^2 table pass.

    PR 6's overlap invariants are preserved (property-tested in
    tests/test_dynamic.py): rows ``[0, boundary_counts[p])`` are exactly
    the rows with off-partition columns, every used send lane lands below
    the receiver's boundary count, and the exchange count per apply stays
    exactly M (the schedule is agnostic to where the tables came from).
    """
    if plan.boundary_counts is None:
        raise ValueError("repair requires a plan built with boundary_counts")
    touched = np.unique(np.asarray(touched, dtype=np.int64))
    if touched.size == 0:
        return plan
    a = np.asarray(adjacency)
    n, n_local, n_parts = plan.n, plan.n_local, plan.n_parts
    n_pad = n_local * n_parts
    old_l_own = np.asarray(plan.l_own)
    old_l_halo = np.asarray(plan.l_halo)
    old_send = np.asarray(plan.send_idx)
    max_halo = old_send.shape[-1]

    # Slot bookkeeping in the *current* plan order. Real vertices occupy
    # slots [0, n) (build asserts it; re-asserted below after the permute).
    ids = np.full(n_pad, -1, dtype=np.int64)
    ids[:n] = plan.order[:n]
    slot_of = np.empty(n, dtype=np.int64)
    slot_of[ids[:n]] = np.arange(n)
    owner_vert = slot_of // n_local  # partition owning each original id

    dirty = sorted(set(int(p) for p in np.unique(owner_vert[touched])))
    dirty_set = set(dirty)

    if plan.pair_counts is not None:
        pair_counts = np.asarray(plan.pair_counts).copy()
    else:
        # Legacy plan: recover used-lane counts from the halo tables
        # (same zero-pattern trick as plan_row_slabs).
        pair_counts = np.zeros((n_parts, n_parts), dtype=np.int64)
        for p in range(n_parts):
            for q in range(n_parts):
                if q == p:
                    continue
                cols = old_l_halo[p][:, q * max_halo : (q + 1) * max_halo]
                pair_counts[p, q] = int(np.any(cols != 0.0, axis=0).sum())

    # --- fresh Laplacian rows + boundary split for every dirty partition ---
    boundary_counts = np.asarray(plan.boundary_counts).copy()
    rows_new: dict[int, np.ndarray] = {}  # p -> (n_local, n) rows, OLD slot order
    perms: dict[int, np.ndarray] = {}
    for p in dirty:
        sl = slice(p * n_local, (p + 1) * n_local)
        ids_p = ids[sl]
        real = ids_p >= 0
        rp = ids_p[real]
        rows = np.zeros((n_local, n))
        rows[real] = -a[rp]
        rows[np.nonzero(real)[0], rp] = a[rp].sum(axis=1, dtype=np.float64)
        rows_new[p] = rows
        own_col = np.zeros(n, dtype=bool)
        own_col[rp] = True
        is_boundary = np.any(rows[:, ~own_col] != 0.0, axis=1)
        boundary_counts[p] = int(is_boundary.sum())
        # Stable boundary-first reorder of the CURRENT local order. Padding
        # rows are all-zero => interior => stay at the tail (stability).
        perms[p] = np.concatenate(
            [np.nonzero(is_boundary)[0], np.nonzero(~is_boundary)[0]]
        )
    n_boundary = max(plan.n_boundary, 1, int(boundary_counts.max()))

    new_ids = ids.copy()
    for p, perm in perms.items():
        sl = slice(p * n_local, (p + 1) * n_local)
        new_ids[sl] = ids[sl][perm]
    assert np.all(new_ids[:n] >= 0), "padding escaped the global tail"
    new_order = new_ids[:n]
    slot_new = np.empty(n, dtype=np.int64)
    slot_new[new_order] = np.arange(n)

    # --- grow max_halo only if a dirty-dirty pair outgrew its lanes -------
    colmasks = {p: np.any(rows_new[p] != 0.0, axis=0) for p in dirty}
    needed = max_halo
    for p in dirty:
        cand = np.nonzero(colmasks[p])[0]
        for q in dirty:
            if q != p:
                needed = max(needed, int((owner_vert[cand] == q).sum()))
    if needed > max_halo:
        l_halo = np.zeros((n_parts, n_local, n_parts * needed), old_l_halo.dtype)
        send_idx = np.zeros((n_parts, n_parts, needed), old_send.dtype)
        for p in range(n_parts):
            for q in range(n_parts):
                if q == p:
                    continue
                cnt = int(pair_counts[p, q])
                l_halo[p][:, q * needed : q * needed + cnt] = old_l_halo[p][
                    :, q * max_halo : q * max_halo + cnt
                ]
                send_idx[q, p, :cnt] = old_send[q, p, :cnt]
        old_l_halo, old_send, max_halo = l_halo, send_idx, needed

    l_own = old_l_own.copy()
    l_halo = old_l_halo.copy()
    send_idx = old_send.copy()

    for p in dirty:
        perm = perms[p]
        inv = np.empty(n_local, dtype=np.int64)
        inv[perm] = np.arange(n_local)
        rows_p = rows_new[p][perm]  # rows in p's NEW local order
        ids_p_new = new_ids[p * n_local : (p + 1) * n_local]
        real = ids_p_new >= 0
        blk = np.zeros((n_local, n_local))
        blk[:, real] = rows_p[:, ids_p_new[real]]
        l_own[p] = blk
        cand = np.nonzero(colmasks[p])[0]
        for q in range(n_parts):
            if q == p:
                continue
            if q in dirty_set:
                t = cand[owner_vert[cand] == q]
                t = t[np.argsort(slot_new[t], kind="stable")]
                cnt = len(t)
                lanes = slot_new[t] - q * n_local
                assert np.all(lanes < boundary_counts[q]), (p, q)
                block = np.zeros((n_local, max_halo), l_halo.dtype)
                block[:, :cnt] = rows_p[:, t]
                l_halo[p][:, q * max_halo : (q + 1) * max_halo] = block
                lane_tbl = np.zeros(max_halo, send_idx.dtype)
                lane_tbl[:cnt] = lanes
                send_idx[q, p] = lane_tbl
                pair_counts[p, q] = cnt
            else:
                # Clean q: identical values/lanes, rows follow p's permute.
                l_halo[p][:, q * max_halo : (q + 1) * max_halo] = old_l_halo[
                    p
                ][perm, q * max_halo : (q + 1) * max_halo]
                cnt = int(pair_counts[q, p])  # lanes q reads from p
                lane_tbl = np.zeros(max_halo, send_idx.dtype)
                lane_tbl[:cnt] = inv[old_send[p, q, :cnt]]
                assert np.all(lane_tbl[:cnt] < boundary_counts[p]), (p, q)
                send_idx[p, q] = lane_tbl

    return PartitionPlan(
        order=new_order,
        l_own=jnp.asarray(l_own, dtype),
        l_halo=jnp.asarray(l_halo, dtype),
        send_idx=jnp.asarray(send_idx),
        halo_words=int(pair_counts.sum()),
        n_local=n_local,
        n=n,
        n_boundary=n_boundary,
        boundary_counts=boundary_counts,
        pair_counts=pair_counts,
    )


def halo_matvec(x_local, l_own, l_halo, send_idx, axis_name: str):
    """One distributed L @ x with halo exchange. Runs inside shard_map.

    Args:
      x_local: (n_local, F) this device's signal slice.
      l_own: (n_local, n_local); l_halo: (n_local, P*max_halo);
      send_idx: (P, max_halo) local indices to send each destination.
    """
    send_buf = x_local[send_idx]  # (P, max_halo) + trailing dims
    recv = jax.lax.all_to_all(send_buf, axis_name, 0, 0, tiled=False)
    halo = recv.reshape((-1,) + x_local.shape[1:])  # (P*max_halo, ...)
    return (jnp.tensordot(l_own, x_local, axes=1)
            + jnp.tensordot(l_halo, halo, axes=1))


def halo_cheb_apply_overlapped(
    f_loc,
    coeffs,
    lmax,
    l_own,
    l_halo,
    send_idx,
    *,
    n_boundary: int,
    axis_name: str,
):
    """Overlapped distributed ``Phi~ f``. Runs inside shard_map.

    Same recurrence and combine as ``chebyshev.cheb_apply`` over
    ``halo_matvec``, but restructured so communication hides behind
    computation: the plan orders each partition's rows boundary-first
    (``n_boundary`` rows with off-partition columns, everything a peer
    ever reads), so step k can

    1. compute only the boundary rows of ``T_k`` (they need just the full
       ``T_{k-1}`` and its halo, both on hand from step k-1),
    2. immediately issue the ``all_to_all`` producing the halo that step
       k+1 consumes,
    3. compute the interior rows of ``T_k`` while that exchange is in
       flight.

    The final step is peeled with no exchange (``T_M``'s halo is never
    consumed), so exactly M exchanges run per apply — the words model
    ``messages_per_apply = M * halo_words`` is unchanged.

    Args:
      f_loc: (n_local, ...) this device's signal slice.
      coeffs: (eta, M+1) union coefficients; lmax: spectrum bound.
      l_own/l_halo/send_idx: this device's plan tables (no leading P axis).
      n_boundary: uniform boundary-block size from the plan (static).

    Returns: (eta,) + f_loc.shape combined outputs, matching
    ``chebyshev.cheb_apply``.
    """
    from repro.core.chebyshev import _outer  # local import to avoid cycle

    b = n_boundary
    coeffs = jnp.asarray(coeffs, dtype=f_loc.dtype)
    alpha = jnp.asarray(lmax, dtype=f_loc.dtype) / 2.0
    order = coeffs.shape[1] - 1

    def exchange(t_boundary):
        """Issue the all_to_all for one Krylov vector's boundary block."""
        send_buf = t_boundary[send_idx]  # send_idx < n_boundary always
        recv = jax.lax.all_to_all(send_buf, axis_name, 0, 0, tiled=False)
        return recv.reshape((-1,) + t_boundary.shape[1:])

    def step_rows(rows, t1, t0, halo1, first):
        """Rows ``rows`` of T_k from full T_{k-1}, T_{k-2} and T_{k-1}'s
        halo — the same shifted recurrence as ``chebyshev.cheb_apply``."""
        lx = (jnp.tensordot(l_own[rows], t1, axes=1)
              + jnp.tensordot(l_halo[rows], halo1, axes=1))
        if first:
            return (lx - alpha * t1[rows]) / alpha
        return (2.0 / alpha) * (lx - alpha * t1[rows]) - t0[rows]

    def overlapped_step(t1, t0, halo1, first, with_exchange):
        """Boundary rows -> issue exchange -> interior rows."""
        tk_b = step_rows(slice(0, b), t1, t0, halo1, first)
        halo_k = exchange(tk_b) if with_exchange else None
        tk_i = step_rows(slice(b, None), t1, t0, halo1, first)
        return jnp.concatenate([tk_b, tk_i], axis=0), halo_k

    t0 = f_loc
    halo0 = exchange(t0[:b])  # T0's boundary values for step 1
    t1, halo1 = overlapped_step(
        t0, t0, halo0, first=True, with_exchange=order >= 2)
    acc = _outer(0.5 * coeffs[:, 0], t0) + _outer(coeffs[:, 1], t1)
    if order < 2:
        return acc

    def body(carry, c_k):
        t1, t0, halo1, acc = carry
        tk, halo_k = overlapped_step(
            t1, t0, halo1, first=False, with_exchange=True)
        acc = acc + _outer(c_k, tk)
        return (tk, t1, halo_k, acc), None

    (t1, t0, halo1, acc), _ = jax.lax.scan(
        body, (t1, t0, halo1, acc),
        jnp.swapaxes(coeffs[:, 2:order], 0, 1))
    # Peeled last step: T_M feeds only the combine, never an exchange.
    tk, _ = overlapped_step(t1, t0, halo1, first=False, with_exchange=False)
    return acc + _outer(coeffs[:, order], tk)


def allgather_matvec(x_local, l_rows, axis_name: str):
    """Naive baseline: all-gather the full signal, multiply own row-slab."""
    x_full = jax.lax.all_gather(x_local, axis_name, axis=0, tiled=True)
    return jnp.tensordot(l_rows, x_full, axes=1)


@partial(jax.jit, static_argnames="scope")
def _take_vertices(x, idx, *, scope: str):
    """``x[..., idx, :]`` on ``x``'s device; an index past the end reads
    zero (the plan's pad slots)."""
    with jax.named_scope(scope):
        return jnp.take(x, idx, axis=-2, mode="fill", fill_value=0)


def _copy_on(y, device) -> jax.Array:
    """``device``'s own copy of ``y``: the buffer it already holds when
    ``y`` is whole there, else one explicit ``device_put``."""
    if isinstance(y, jax.Array) and y.sharding.is_fully_replicated:
        for shard in y.addressable_shards:
            if shard.device == device:
                return shard.data
    return jax.device_put(y, device)


def _waited(fn):
    """``fn``, returning once its result is ready.

    XLA:CPU runs each device's share of a collective program on one shared
    thread pool, and a program dispatched while an earlier one still runs
    can take the threads that the earlier one's rendezvous waits for: the
    process then aborts. So on the CPU the mesh programs run one at a
    time; on an accelerator each device runs its programs in order, and
    the result stays asynchronous.
    """

    def run(*args):
        out = fn(*args)
        out.block_until_ready()
        return out

    return run


class _MeshLayout:
    """What both context classes share: the plans' operands placed on the
    mesh once, and the permutation between the caller's vertex order and
    the partition order run on the device.

    Scatter takes the signal with the padded order on the signal's own
    device (pad slots read zero) and places it on ``P(axis)`` by one
    explicit ``device_put``. Every program all-gathers its result over the
    mesh before it returns, so each device holds it whole; gather takes
    the caller's device's copy and inverts the order there. Neither
    direction stages the signal in host memory.
    """

    def _place(self, plans) -> tuple[PartitionPlan, ...]:
        """``plans`` with their tables on ``P(axis)``; the layout's indices
        on the default device."""
        shard = NamedSharding(self.mesh, P(self.axis))
        placed = tuple(
            dataclasses.replace(
                p, l_own=jax.device_put(p.l_own, shard),
                l_halo=jax.device_put(p.l_halo, shard),
                send_idx=jax.device_put(p.send_idx, shard))
            for p in plans)
        plan = placed[0]
        fwd = np.full(plan.n_local * plan.n_parts, plan.n, np.int32)
        fwd[: plan.n] = plan.order  # pad slots index past the end
        inv = np.empty(plan.n, np.int32)
        inv[plan.order] = np.arange(plan.n)
        self._dev.update(fwd=jax.device_put(fwd), inv=jax.device_put(inv))
        return placed

    def replicate(self, x, dtype) -> jax.Array:
        """``x`` in ``dtype``, whole on every device of the mesh: as given
        when it is already, else through one explicit ``device_put``."""
        x = x.astype(dtype) if isinstance(x, jax.Array) else np.asarray(x, dtype)
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def constant(self, name: str, value, dtype) -> jax.Array:
        """:meth:`replicate` of ``value``, placed on the first call for
        ``(name, dtype)`` and held for every later one."""
        key = (name, np.dtype(dtype))
        if key not in self._dev:
            self._dev[key] = self.replicate(value, dtype)
        return self._dev[key]

    def scatter_signal(self, f) -> jax.Array:
        """Permute+pad an (N,) or (..., N, F) signal and shard it over the
        devices: (..., P*n_local, F) sharded along the vertex axis."""
        f = jnp.asarray(f)
        if f.ndim == 1:
            f = jnp.expand_dims(f, -1)
        fp = _take_vertices(f, self._dev["fwd"], scope="repro.halo.forward_perm")
        spec = P(*([None] * (f.ndim - 2)), self.axis)
        return jax.device_put(fp, NamedSharding(self.mesh, spec))

    def gather_signal(self, y, device=None) -> jax.Array:
        """Invert scatter: (..., P*n_local, F) -> (..., N, F) in input
        order, on ``device`` (default: the default device). A result of
        this context's programs is already whole on every device, so no
        copy leaves the device that holds it."""
        y = _copy_on(y, device or jax.devices()[0])
        return _take_vertices(y, self._dev["inv"], scope="repro.halo.inverse_perm")

    def _program(self, key, local_fn, in_specs, out_specs):
        """``jit(shard_map(local_fn))``, cached under ``key``; its
        ``out_specs`` result is all-gathered over the mesh on the way out."""
        fn = self._programs.get(key)
        if fn is None:
            fn = jax.jit(
                shard_map(local_fn, mesh=self.mesh,
                          in_specs=in_specs, out_specs=out_specs),
                out_shardings=NamedSharding(self.mesh, P()))
            if self.mesh.devices.flat[0].platform == "cpu":
                fn = _waited(fn)
            self._programs[key] = fn
        return fn


@dataclasses.dataclass(frozen=True)
class DistributedGraphContext(_MeshLayout):
    """Binds a PartitionPlan to a mesh axis and exposes distributed ops.

    Compiled shard_map programs are cached per (backend, schedule) in
    ``_programs`` — coefficients and ``lmax`` enter as runtime arguments,
    so one traced program serves every filter order/eta combination
    (apply and gram reuse the same cache entry) instead of re-tracing the
    collective program on every call. ``plan`` holds its tables on the
    mesh from construction on.
    """

    plan: PartitionPlan
    mesh: Mesh
    axis: str
    _programs: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _dev: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)

    def __post_init__(self):
        if self.mesh is not None:
            object.__setattr__(self, "plan", self._place((self.plan,))[0])

    def cheb_apply(
        self, f_sharded, coeffs, lmax, backend: str = "halo",
        overlap: bool = True,
    ):
        """Distributed ``Phi~ f`` (Algorithm 1 on the mesh).

        Prefer ``repro.filters.GraphFilter.apply(f, backend="halo")`` —
        it builds the plan/mesh and handles scatter/gather; this method
        is the underlying engine (and shim for pre-sharded callers).

        f_sharded: (P*n_local, F) sharded along ``axis``.
        overlap: halo backend only — use the overlapped schedule
          (:func:`halo_cheb_apply_overlapped`, default) or the serial
          exchange->matvec reference (``overlap=False``); identical
          results up to f32 rounding, same message count.
        coeffs, lmax: placed by :meth:`replicate` unless they already are.
        Returns (eta, P*n_local, F), whole on every device of the mesh.
        """
        from repro.core import chebyshev  # local import to avoid cycle

        plan = self.plan
        coeffs = self.replicate(coeffs, f_sharded.dtype)
        lmax = self.replicate(lmax, f_sharded.dtype)
        axis = self.axis

        if backend == "halo":
            if overlap:

                def local_fn(f_loc, coeffs, lmax, l_own, l_halo, send_idx):
                    return halo_cheb_apply_overlapped(
                        f_loc, coeffs, lmax,
                        l_own[0], l_halo[0], send_idx[0],
                        n_boundary=plan.n_boundary, axis_name=axis)

            else:

                def local_fn(f_loc, coeffs, lmax, l_own, l_halo, send_idx):
                    mv = lambda v: halo_matvec(
                        v, l_own[0], l_halo[0], send_idx[0], axis)
                    return chebyshev.cheb_apply(mv, f_loc, coeffs, lmax)

            fn = self._program(
                ("halo", bool(overlap)), local_fn,
                in_specs=(P(axis), P(None, None), P(),
                          P(axis), P(axis), P(axis)),
                out_specs=P(None, axis))
            return fn(f_sharded, coeffs, lmax,
                      plan.l_own, plan.l_halo, plan.send_idx)

        elif backend == "allgather":
            l_rows = self._dev.get("l_rows")
            if l_rows is None:
                l_rows = self._dev["l_rows"] = jax.device_put(
                    plan_row_slabs(plan), NamedSharding(self.mesh, P(axis)))

            def local_fn(f_loc, coeffs, lmax, l_rows_loc):
                mv = lambda v: allgather_matvec(v, l_rows_loc[0], axis)
                return chebyshev.cheb_apply(mv, f_loc, coeffs, lmax)

            fn = self._program(
                "allgather", local_fn,
                in_specs=(P(axis), P(None, None), P(), P(axis)),
                out_specs=P(None, axis))
            return fn(f_sharded, coeffs, lmax, l_rows)
        raise ValueError(f"unknown backend {backend!r}")

    def cheb_adjoint(self, a_sharded, coeffs, lmax):
        """Distributed ``Phi~* a`` (paper Sec. IV-B: length-eta messages).

        a_sharded: (eta, P*n_local, F) sharded along the vertex axis.
        Returns (P*n_local, F), whole on every device of the mesh."""
        from repro.core import chebyshev

        plan = self.plan
        coeffs = self.replicate(coeffs, a_sharded.dtype)
        lmax = self.replicate(lmax, a_sharded.dtype)
        axis = self.axis

        def local_fn(a_loc, coeffs, lmax, l_own, l_halo, send_idx):
            mv = lambda v: halo_matvec(
                v, l_own[0], l_halo[0], send_idx[0], axis)
            return chebyshev.cheb_adjoint_apply(mv, a_loc, coeffs, lmax)

        fn = self._program(
            "halo_adjoint", local_fn,
            in_specs=(P(None, self.axis), P(None, None), P(),
                      P(axis), P(axis), P(axis)),
            out_specs=P(axis))
        return fn(a_sharded, coeffs, lmax,
                  plan.l_own, plan.l_halo, plan.send_idx)

    def gram_apply(self, f_sharded, op, backend: str = "halo",
                   overlap: bool = True):
        """Distributed ``Phi~* Phi~ f`` as one degree-2M filter
        (Sec. IV-C, 4M|E| messages)."""
        out = self.cheb_apply(
            f_sharded, np.asarray(op.gram_coeffs)[None, :], op.lmax,
            backend=backend, overlap=overlap)
        return out[0]

    def messages_per_apply(self, order: int, backend: str = "halo") -> int:
        """Scalar words moved per ``Phi~ f`` (excluding padding slots).

        The paper's radio model (Sec. IV-A) bounds one apply of a union
        filter by ``2 M |E|`` length-1 messages: each of the M recurrence
        orders transmits every vertex value across every incident edge,
        in both directions. On the device mesh the analogous counts are:

        * ``halo`` — ``M * halo_words`` where ``halo_words`` sums, over
          ordered partition pairs (p, q), the boundary vertices of q that
          p's Laplacian rows touch. ``halo_words <= 2|E|`` always: a
          boundary vertex is sent once per neighbouring *partition*
          rather than once per edge (the mesh enjoys the same broadcast
          saving the paper notes for radio nodes), so the halo backend
          never exceeds the paper bound and typically lands far under it.
        * ``allgather`` — ``M * n_local * P * (P - 1)``: every order,
          every device ships its whole slab to all P-1 peers regardless
          of the cut size. Independent of |E| — the baseline that makes
          the halo saving measurable.

        Single-device backends (dense, bsr) move no network words; the
        grid backend's count is ``M * 2 * (P-1) * side`` (one boundary
        row per direction per seam per order — see
        ``repro.filters.GraphFilter.messages_per_apply``).

        Parameters
        ----------
        order : int
            Chebyshev truncation order M of the applied filter.
        backend : {"halo", "allgather"}
            Which distributed matvec's communication model to count.

        Returns
        -------
        int
            Scalar words exchanged across all devices for one apply of a
            single (N,) signal; multiply by F for an (N, F) batch and by
            ``eta`` for adjoint message *lengths* (Sec. IV-B).
        """
        if backend == "halo":
            return order * self.plan.halo_words
        n_dev = self.plan.n_parts
        return order * self.plan.n_local * n_dev * (n_dev - 1)


@dataclasses.dataclass(frozen=True)
class MultiShiftGraphContext(_MeshLayout):
    """Distributed context for a multi-shift joint filter (DESIGN.md
    Sec. 11): R per-shift :class:`PartitionPlan` s over ONE shared vertex
    layout (built by :func:`build_shift_partition_plans`), bound to a mesh
    axis.

    The joint recurrence runs inside a single ``shard_map`` program whose
    per-shift matvec closures each do their own halo exchange — so one
    scatter and one gather move the signal, while every matvec in shift r
    moves exactly ``plans[r].halo_words`` words. Words per apply is the
    per-shift sum ``sum_r count_r * halo_words_r`` with
    ``count_r = M_r * prod_{s<r}(M_s + 1)``
    (:func:`repro.filters.shift_matvec_counts`).
    """

    plans: tuple[PartitionPlan, ...]
    mesh: Mesh
    axis: str
    lmaxes: tuple[float, ...]
    _programs: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _dev: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)

    def __post_init__(self):
        if self.mesh is not None:
            object.__setattr__(self, "plans", self._place(self.plans))

    @property
    def plan(self) -> PartitionPlan:
        """The first shift's plan — layout fields (order, n_local, n) are
        shared by construction, so scatter/gather and words accounting
        that only need the layout read them here."""
        return self.plans[0]

    def _tables(self):
        out = []
        for plan in self.plans:
            out.extend([plan.l_own, plan.l_halo, plan.send_idx])
        return tuple(out)

    def _joint_program(self, key, local_fn, lead_specs, out_specs):
        table_specs = (P(self.axis),) * (3 * len(self.plans))
        return self._program(key, local_fn, lead_specs + table_specs, out_specs)

    def cheb_apply_joint(self, f_sharded, coeffs):
        """Distributed joint ``Phi~ f``: per-shift halo exchange inside one
        shard_map program. f_sharded: (P*n_local, F) sharded along the
        vertex axis; coeffs: (eta, M_1+1, ..., M_R+1). Returns
        (eta, P*n_local, F), whole on every device of the mesh."""
        from repro.core import chebyshev  # local import to avoid cycle

        r = len(self.plans)
        lmaxes = self.lmaxes
        axis = self.axis
        coeffs = self.replicate(coeffs, f_sharded.dtype)

        def local_fn(f_loc, coeffs, *tables):
            mvs = [
                partial(
                    halo_matvec,
                    l_own=tables[3 * i][0],
                    l_halo=tables[3 * i + 1][0],
                    send_idx=tables[3 * i + 2][0],
                    axis_name=axis,
                )
                for i in range(r)
            ]
            return chebyshev.cheb_apply_joint(mvs, f_loc, coeffs, lmaxes)

        fn = self._joint_program(
            "joint_apply", local_fn,
            lead_specs=(P(axis), P(*([None] * (r + 1)))),
            out_specs=P(None, axis))
        return fn(f_sharded, coeffs, *self._tables())

    def cheb_adjoint_joint(self, a_sharded, coeffs):
        """Distributed joint ``Phi~* a`` for a_sharded shaped
        (eta, P*n_local, F) sharded along the vertex axis; returns
        (P*n_local, F), whole on every device of the mesh."""
        from repro.core import chebyshev

        r = len(self.plans)
        lmaxes = self.lmaxes
        axis = self.axis
        coeffs = self.replicate(coeffs, a_sharded.dtype)

        def local_fn(a_loc, coeffs, *tables):
            mvs = [
                partial(
                    halo_matvec,
                    l_own=tables[3 * i][0],
                    l_halo=tables[3 * i + 1][0],
                    send_idx=tables[3 * i + 2][0],
                    axis_name=axis,
                )
                for i in range(r)
            ]
            return chebyshev.cheb_adjoint_apply_joint(
                mvs, a_loc, coeffs, lmaxes)

        fn = self._joint_program(
            "joint_adjoint", local_fn,
            lead_specs=(P(None, axis), P(*([None] * (r + 1)))),
            out_specs=P(axis))
        return fn(a_sharded, coeffs, *self._tables())

    def messages_per_apply(self, matvec_counts) -> int:
        """Per-shift words sum: shift r's ``count_r`` matvecs each move
        its own plan's ``halo_words``."""
        return int(sum(
            int(c) * p.halo_words
            for c, p in zip(matvec_counts, self.plans)
        ))


# ------------------------------------------------------------------------
# Production-scale grid workload: matrix-free stencil Laplacian with
# row-slab partitioning. The general PartitionPlan above stores dense
# per-pair halo tables (fine for sensor graphs up to ~10^4 vertices); at
# 10^5-10^6 vertices on 256-512 chips the Laplacian must stay implicit —
# each Chebyshev order exchanges exactly one boundary row with each slab
# neighbour via ppermute (the mesh analog of Algorithm 1's per-neighbour
# radio messages, and the TPU-idiomatic halo pattern).
# ------------------------------------------------------------------------


def grid_slab_matvec(x_local, *, side: int, axis_names, n_parts: int):
    """L @ x for a non-periodic 4-neighbour unit-weight grid, one row-slab
    per device. Runs inside shard_map; x_local: (rows_per * side, F).

    Communication: 2 ppermute sends of one (side, F) boundary row.
    """
    rows_per = x_local.shape[0] // side
    f = x_local.shape[-1]
    x3 = x_local.reshape(rows_per, side, f)
    idx = jax.lax.axis_index(axis_names)

    fwd = [(i, i + 1) for i in range(n_parts - 1)]
    bwd = [(i + 1, i) for i in range(n_parts - 1)]
    # neighbour-above's last row / neighbour-below's first row (zeros at
    # the global boundary: ppermute delivers 0 where no sender exists).
    halo_up = jax.lax.ppermute(x3[-1], axis_names, fwd)
    halo_dn = jax.lax.ppermute(x3[0], axis_names, bwd)

    up = jnp.concatenate([halo_up[None], x3[:-1]], axis=0)
    dn = jnp.concatenate([x3[1:], halo_dn[None]], axis=0)
    left = jnp.pad(x3[:, :-1], ((0, 0), (1, 0), (0, 0)))
    right = jnp.pad(x3[:, 1:], ((0, 0), (0, 1), (0, 0)))

    gr = idx * rows_per + jnp.arange(rows_per)  # global row ids
    col = jnp.arange(side)
    deg = (4.0
           - (gr == 0).astype(x_local.dtype)[:, None]
           - (gr == side - 1).astype(x_local.dtype)[:, None]
           - (col == 0).astype(x_local.dtype)[None, :]
           - (col == side - 1).astype(x_local.dtype)[None, :])
    y = deg[..., None] * x3 - up - dn - left - right
    return y.reshape(x_local.shape)


def grid_allgather_matvec(x_local, *, side: int, axis_names, n_parts: int):
    """Naive baseline: all-gather the full field, stencil on the slab."""
    rows_per = x_local.shape[0] // side
    f = x_local.shape[-1]
    idx = jax.lax.axis_index(axis_names)
    x_full = jax.lax.all_gather(x_local, axis_names, axis=0, tiled=True)
    full3 = x_full.reshape(side, side, f)
    padded = jnp.pad(full3, ((1, 1), (0, 0), (0, 0)))
    start = idx * rows_per
    x3 = jax.lax.dynamic_slice_in_dim(full3, start, rows_per, axis=0)
    up = jax.lax.dynamic_slice_in_dim(padded, start, rows_per, axis=0)
    dn = jax.lax.dynamic_slice_in_dim(padded, start + 2, rows_per, axis=0)
    left = jnp.pad(x3[:, :-1], ((0, 0), (1, 0), (0, 0)))
    right = jnp.pad(x3[:, 1:], ((0, 0), (0, 1), (0, 0)))
    gr = idx * rows_per + jnp.arange(rows_per)
    col = jnp.arange(side)
    deg = (4.0
           - (gr == 0).astype(x_local.dtype)[:, None]
           - (gr == side - 1).astype(x_local.dtype)[:, None]
           - (col == 0).astype(x_local.dtype)[None, :]
           - (col == side - 1).astype(x_local.dtype)[None, :])
    y = deg[..., None] * x3 - up - dn - left - right
    return y.reshape(x_local.shape)


def grid_cheb_apply_ca(
    f_local: jax.Array,
    coeffs: jax.Array,
    lmax: float,
    *,
    side: int,
    axis_names,
    n_parts: int,
    depth: int = 2,
):
    """Communication-avoiding Chebyshev application on the grid slabs
    (beyond-paper: matrix-powers-kernel for the 3-term recurrence).

    Instead of one boundary-row exchange per Chebyshev order (Algorithm 1),
    exchange a ``depth``-row halo once and run ``depth`` recurrence steps
    locally on the extended slab — the per-order byte volume is unchanged
    (depth rows per depth orders) but the number of neighbour rounds drops
    by ``depth`` (latency, the halo cell's bottleneck at production F).

    Ghost rows outside the global grid are re-zeroed after every local
    step, which together with the boundary-degree stencil reproduces the
    non-periodic Laplacian exactly. Requires depth <= rows-per-slab (one-hop
    neighbours hold the whole halo).

    f_local: (rows_per * side, F) inside shard_map. Returns
    (eta, rows_per*side, F) — matches chebyshev.cheb_apply output layout.
    """
    rows_per = f_local.shape[0] // side
    assert 1 <= depth <= rows_per, (depth, rows_per)
    fdim = f_local.shape[-1]
    coeffs = jnp.asarray(coeffs, f_local.dtype)
    eta, m_plus1 = coeffs.shape
    order = m_plus1 - 1
    alpha = jnp.asarray(lmax, f_local.dtype) / 2.0
    idx = jax.lax.axis_index(axis_names)

    fwd = [(i, i + 1) for i in range(n_parts - 1)]
    bwd = [(i + 1, i) for i in range(n_parts - 1)]

    col = jnp.arange(side)
    col_deg = ((col == 0) | (col == side - 1)).astype(f_local.dtype)

    def local_step(t1e, t0e, gr_ext):
        """One recurrence step on an extended slab (loses 1 ghost row per
        side). t1e/t0e: (R_ext, side, F); returns (R_ext-2, side, F)."""
        deg = (4.0
               - (gr_ext == 0).astype(t1e.dtype)[:, None]
               - (gr_ext == side - 1).astype(t1e.dtype)[:, None]
               - col_deg[None, :])
        up = t1e[:-2]
        dn = t1e[2:]
        mid = t1e[1:-1]
        left = jnp.pad(mid[:, :-1], ((0, 0), (1, 0), (0, 0)))
        right = jnp.pad(mid[:, 1:], ((0, 0), (0, 1), (0, 0)))
        lx = deg[1:-1, :, None] * mid - up - dn - left - right
        t_new = (2.0 / alpha) * (lx - alpha * mid) - t0e[1:-1]
        # zero rows outside the global domain (non-periodic boundary)
        valid = ((gr_ext[1:-1] >= 0) & (gr_ext[1:-1] < side))
        return t_new * valid[:, None, None].astype(t_new.dtype)

    def exchange(t, d):
        """Extend a (rows_per, side, F) slab with d ghost rows per side."""
        top_halo = jax.lax.ppermute(t[-d:], axis_names, fwd)   # from above
        bot_halo = jax.lax.ppermute(t[:d], axis_names, bwd)    # from below
        return jnp.concatenate([top_halo, t, bot_halo], axis=0)

    f3 = f_local.reshape(rows_per, side, fdim)
    gr_base = idx * rows_per + jnp.arange(rows_per)

    # T0 = f ; T1 = (L - aI) f / a  (one depth-1 exchange)
    t0 = f3
    t0e = exchange(t0, 1)
    deg = (4.0
           - (gr_base == 0).astype(f3.dtype)[:, None]
           - (gr_base == side - 1).astype(f3.dtype)[:, None]
           - col_deg[None, :])
    up = t0e[:-2]
    dn = t0e[2:]
    left = jnp.pad(t0[:, :-1], ((0, 0), (1, 0), (0, 0)))
    right = jnp.pad(t0[:, 1:], ((0, 0), (0, 1), (0, 0)))
    lx = deg[:, :, None] * t0 - up - dn - left - right
    t1 = lx / alpha - t0

    acc = (0.5 * coeffs[:, 0, None, None, None] * t0[None]
           + coeffs[:, 1, None, None, None] * t1[None])

    # remaining orders in blocks of `depth`, overlapped: each block's
    # ghost exchange is issued BEFORE the previous block's deferred
    # eta-combine accumulations, so the 2 ppermutes per block hide
    # behind the (eta x d) combine flops instead of serializing.
    def exchange_block(t1, t0, d):
        # pack the T_{k-1} (depth d) and T_{k-2} (depth d-1, padded to d)
        # ghosts into ONE neighbour message per direction: the round count
        # per block is 2 ppermutes regardless of depth — the entire point
        # of the communication-avoiding schedule.
        packed = jnp.stack([t1, t0], axis=0)  # (2, rows_per, side, F)
        top_halo = jax.lax.ppermute(packed[:, -d:], axis_names, fwd)
        bot_halo = jax.lax.ppermute(packed[:, :d], axis_names, bwd)
        return jnp.concatenate([top_halo, packed, bot_halo], axis=1)

    k = 2
    if k <= order:
        ext = exchange_block(t1, t0, min(depth, order - k + 1))
    while k <= order:
        d = min(depth, order - k + 1)
        t1e, t0e = ext[0], ext[1]
        gr_ext = jnp.concatenate([
            gr_base[:1] + jnp.arange(-d, 0),
            gr_base,
            gr_base[-1:] + jnp.arange(1, d + 1)])
        interiors = []
        for j in range(d):
            t_new_ext = local_step(t1e, t0e, gr_ext)
            # shrink: t0 <- t1 (trimmed), t1 <- t_new
            t0e = t1e[1:-1]
            t1e = t_new_ext
            gr_ext = gr_ext[1:-1]
            trim = d - j - 1
            interiors.append(t_new_ext[trim: t_new_ext.shape[0] - trim]
                             if trim else t_new_ext)
        # after d steps both t1e and t0e are ghost-free (rows_per, ...)
        t0 = t0e
        t1 = t1e
        k_block = k
        k += d
        if k <= order:
            # issue the next block's exchange first, combine while it flies
            ext = exchange_block(t1, t0, min(depth, order - k + 1))
        for j, interior in enumerate(interiors):
            acc = acc + (coeffs[:, k_block + j, None, None, None]
                         * interior[None])

    return acc.reshape(eta, rows_per * side, fdim)


def plan_row_slabs(plan: PartitionPlan) -> jax.Array:
    """Reassemble (P, n_local, N_pad) full row-slabs (allgather backend)."""
    n_parts, n_local = plan.l_own.shape[0], plan.n_local
    max_halo = plan.send_idx.shape[-1]
    rows = np.zeros((n_parts, n_local, n_parts * n_local), dtype=np.float32)
    l_own = np.asarray(plan.l_own)
    l_halo = np.asarray(plan.l_halo)
    send_idx = np.asarray(plan.send_idx)
    for p in range(n_parts):
        sl = slice(p * n_local, (p + 1) * n_local)
        rows[p][:, sl] = l_own[p]
        for q in range(n_parts):
            if q == p:
                continue
            cols = l_halo[p][:, q * max_halo : (q + 1) * max_halo]
            used = np.any(cols != 0.0, axis=0)
            idx = send_idx[q, p][used] + q * n_local
            rows[p][:, idx] = cols[:, used]
    return jnp.asarray(rows)
