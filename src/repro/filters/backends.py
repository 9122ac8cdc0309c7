"""The shipped ``GraphFilter`` backends (DESIGN.md Sec. 6.2).

Five graph-bound substrates plus one graph-free escape hatch:

* ``dense``      — jnp reference: dense Laplacian matvec, ``lax.scan``
                   recurrence. The parity oracle for everything else.
* ``bsr``        — Pallas Block-ELL: the fused union-combine kernel when
                   the VMEM budget allows (one ``pallas_call`` per apply),
                   the stepwise per-order chain otherwise.
* ``halo``       — ``shard_map`` vertex partition, per-order boundary
                   (halo) exchange via ``all_to_all`` — Algorithm 1 on the
                   device mesh.
* ``allgather``  — naive distributed baseline: full-signal all-gather per
                   order (the §Perf "before" configuration).
* ``grid``       — matrix-free stencil Laplacian on row slabs with the
                   communication-avoiding depth-d schedule (square grid
                   graphs only).
* ``matvec``     — no graph: the caller supplies ``matvec=`` computing
                   ``L @ v`` (legacy entry point; keeps ``apps/`` shims and
                   exotic operators working).

All backends share the same numerics: the eq. 9 recurrence in f32 with the
eq. 11 coefficient combine, so outputs agree to float tolerance (enforced
by ``tests/test_filters.py``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.core.compat import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import chebyshev
from repro.core import graph as graph_lib
from repro.core.distributed import (
    DistributedGraphContext,
    MultiShiftGraphContext,
    build_partition_plan,
    build_shift_partition_plans,
    grid_cheb_apply_ca,
    grid_slab_matvec,
)
from repro.filters.api import bucket_size
from repro.filters.registry import BackendCapabilities, register_backend
from repro.kernels import autotune, ops as kops, ref as kref

__all__ = [
    "DenseBackend",
    "BsrBackend",
    "HaloBackend",
    "AllgatherBackend",
    "GridBackend",
    "MatvecBackend",
]


def _require_graph(filt, name: str):
    if filt.graph is None:
        raise ValueError(
            f"backend {name!r} needs a bound graph; build the filter with "
            "graph=... or call filt.bind(graph)"
        )
    return filt.graph


def _coeffs_or(filt, coeffs) -> np.ndarray:
    return np.atleast_2d(
        np.asarray(filt.coeffs if coeffs is None else coeffs)
    )


def _default_mesh(axis: str, n_parts: int | None) -> Mesh:
    n = n_parts or len(jax.devices())
    return compat.make_mesh((n,), (axis,))


# Power-of-two shape buckets (shared with the serving engine's panel
# cache): the restricted delta apply compiles once per bucket, not once
# per frame. Floor 32 = bucket_size's default.


@jax.jit
def _restricted_cheb_apply(lap_sub, d_sub, coeffs, lmax):
    """Recurrence on the induced submatrix over the order-hop reach.

    Exact, not approximate: every length-k walk (k <= M) from the delta's
    support stays inside the M-hop neighbourhood, so the polynomial in the
    *submatrix* of L (true degrees on the diagonal) agrees with the full
    filter on that neighbourhood — see DESIGN.md Sec. 8.
    """
    return chebyshev.cheb_apply(lambda v: lap_sub @ v, d_sub, coeffs, lmax)


@register_backend
class MatvecBackend:
    """Graph-free backend: the caller supplies the Laplacian action.

    ``filt.apply(f, backend="matvec", matvec=fn)`` runs the recurrence with
    ``fn(v) = L @ v`` — any linear map with the Laplacian's symmetry. This
    is the abstraction the rest of the repo was originally written against
    and remains the escape hatch for operators no packaged backend covers.
    """

    name = "matvec"
    prepare_opts: frozenset[str] = frozenset()
    # traceable: pure jax iff the caller's matvec is; assume so.
    capabilities = BackendCapabilities(traceable=True)

    def prepare(self, filt, **_):
        return None

    def apply(self, filt, state, f, *, coeffs=None, matvec=None, **_):
        if matvec is None:
            raise ValueError("backend 'matvec' requires matvec=")
        c = _coeffs_or(filt, coeffs)
        return chebyshev.cheb_apply(matvec, f, c, filt.lmax)

    def adjoint(self, filt, state, a, *, matvec=None, **_):
        if matvec is None:
            raise ValueError("backend 'matvec' requires matvec=")
        return chebyshev.cheb_adjoint_apply(matvec, a, filt.coeffs, filt.lmax)

    def messages_per_apply(self, filt, state, matvec_counts) -> int:
        return 0


@register_backend
class DenseBackend:
    """jnp reference backend: dense Laplacian, ``lax.scan`` recurrence."""

    name = "dense"
    prepare_opts: frozenset[str] = frozenset()
    capabilities = BackendCapabilities(
        traceable=True, sparse_input=True, multi_shift=True
    )

    def prepare(self, filt, **_):
        g = _require_graph(filt, self.name)
        if filt.n_shifts > 1:
            # One dense Laplacian per shift; apply branches on the tuple.
            return tuple(s.laplacian() for s in filt.shifts)
        return g.laplacian()

    def apply_sparse(
        self, filt, lap, delta, support, *, coeffs=None, reach=None, **_
    ):
        """``Phi~ delta`` for ``delta`` supported on ``support``: run the
        recurrence on the induced submatrix over the M-hop reach only.

        The submatrix size is rounded up to a power-of-two bucket so a
        stream of slightly-varying change sets reuses a handful of
        compiled programs instead of retracing every frame. ``reach=``
        takes a precomputed M-hop neighbourhood mask (the streaming layer
        already walks it for the words accounting); when omitted it is
        recomputed here.
        """
        c = _coeffs_or(filt, coeffs)
        g = _require_graph(filt, self.name)
        order = c.shape[1] - 1
        if reach is None:
            reach = graph_lib.khop_neighborhood(g.adjacency, support, order)
        idx = np.nonzero(reach)[0]
        delta = jnp.asarray(delta)
        n = delta.shape[0]
        b = bucket_size(len(idx), n)
        if b >= n:
            # Reach covers (almost) the whole graph — restriction buys
            # nothing; the full apply is the same work without the scatter.
            return self.apply(filt, lap, delta, coeffs=coeffs)
        squeeze = delta.ndim == 1
        d2 = delta[:, None] if squeeze else delta
        lap_sub = jnp.zeros((b, b), lap.dtype)
        lap_sub = lap_sub.at[: len(idx), : len(idx)].set(lap[idx][:, idx])
        d_sub = jnp.zeros((b,) + d2.shape[1:], d2.dtype).at[: len(idx)].set(d2[idx])
        out_sub = _restricted_cheb_apply(
            lap_sub, d_sub, jnp.asarray(c, d2.dtype), jnp.asarray(filt.lmax, d2.dtype)
        )
        out = jnp.zeros((c.shape[0],) + d2.shape, d2.dtype)
        out = out.at[:, idx].set(out_sub[:, : len(idx)])
        return out[:, :, 0] if squeeze else out

    def apply(self, filt, lap, f, *, coeffs=None, **_):
        c = _coeffs_or(filt, coeffs)
        if isinstance(lap, tuple):
            mvs = [
                lambda v, m=m: jnp.tensordot(m, v, axes=1) for m in lap
            ]
            return chebyshev.cheb_apply_joint(mvs, f, c, filt.shift_lmaxes)
        return chebyshev.cheb_apply(lambda v: lap @ v, f, c, filt.lmax)

    def adjoint(self, filt, lap, a, **_):
        # tensordot (not @): the adjoint recurrence carries the eta blocks
        # in trailing dims, so contract the vertex axis explicitly.
        if isinstance(lap, tuple):
            mvs = [
                lambda v, m=m: jnp.tensordot(m, v, axes=1) for m in lap
            ]
            return chebyshev.cheb_adjoint_apply_joint(
                mvs, a, filt.coeffs, filt.shift_lmaxes
            )
        return chebyshev.cheb_adjoint_apply(
            lambda v: jnp.tensordot(lap, v, axes=1), a, filt.coeffs,
            filt.lmax,
        )

    def messages_per_apply(self, filt, state, matvec_counts) -> int:
        return 0


@dataclasses.dataclass(frozen=True)
class _BsrState:
    bell: kref.BlockEll
    perm: np.ndarray  # vertex permutation applied before tiling
    inv: np.ndarray  # positions of the true vertices in permuted order
    n: int  # true vertex count
    n_pad: int


@dataclasses.dataclass(frozen=True)
class _BsrMultiState:
    """Multi-shift Block-ELL state: one tiling per shift, shared layout.

    Every shift's Laplacian is permuted by the SAME spatial order (and
    padded to the same ``n_pad``) so the joint recurrence interleaves
    per-shift matvecs on one signal layout — the single-chip analog of
    the shared-layout partition plans.
    """

    bells: tuple
    perm: np.ndarray
    inv: np.ndarray
    n: int
    n_pad: int


@register_backend
class BsrBackend:
    """Pallas Block-ELL backend (DESIGN.md Sec. 3 + 6.3).

    ``prepare`` spatially reorders the vertices (recursive coordinate
    bisection) so nonzeros cluster into dense MXU tiles, then converts the
    Laplacian to Block-ELL. ``apply`` picks the fused union-combine kernel
    when the autotune table says the VMEM working set fits, else chains the
    stepwise kernel.

    Options: ``block_size`` (prepare; default 8), ``interpret`` (default:
    True on the CPU backend only), ``f_tile`` / ``fuse`` overrides, and
    ``krylov_dtype`` (apply; default f32 — ``"bfloat16"`` halves the
    kernels' Krylov working set while all combines stay f32, widening
    the fused-kernel regime in ``autotune.select_tiling``).
    """

    name = "bsr"
    prepare_opts: frozenset[str] = frozenset({"block_size"})
    # traceable: pallas_call (or interpret mode) traces fine in scan.
    capabilities = BackendCapabilities(traceable=True, multi_shift=True)

    def prepare(self, filt, *, block_size: int = 8, **_):
        g = _require_graph(filt, self.name)
        n = g.n_vertices
        if g.coords is not None:
            perm = graph_lib.spatial_partition_order(
                np.asarray(g.coords), max(n // block_size, 1)
            )
        else:
            perm = np.arange(n)
        inv = np.empty(n, dtype=np.int64)
        inv[perm] = np.arange(n)
        if filt.n_shifts > 1:
            bells = tuple(
                kref.bsr_from_dense(
                    np.asarray(s.laplacian(), np.float64)[
                        np.ix_(perm, perm)
                    ],
                    block_size,
                )
                for s in filt.shifts
            )
            return _BsrMultiState(
                bells=bells, perm=perm, inv=inv, n=n, n_pad=bells[0].n
            )
        lap = np.asarray(g.laplacian(), np.float64)
        bell = kref.bsr_from_dense(lap[np.ix_(perm, perm)], block_size)
        return _BsrState(bell=bell, perm=perm, inv=inv, n=n, n_pad=bell.n)

    def _forward(self, state: _BsrState, f):
        """Permute + pad an (N, ...) signal into kernel layout."""
        f = jnp.asarray(f)
        squeeze = f.ndim == 1
        f2 = f[:, None] if squeeze else f
        fp = jnp.zeros((state.n_pad,) + f2.shape[1:], f2.dtype)
        fp = fp.at[: state.n].set(f2[state.perm])
        return fp, squeeze

    def apply(
        self,
        filt,
        state: _BsrState,
        f,
        *,
        coeffs=None,
        interpret: bool | None = None,
        f_tile: int | None = None,
        fuse: bool | None = None,
        krylov_dtype=None,
        **_,
    ):
        c = _coeffs_or(filt, coeffs)
        if interpret is None:
            # Interpret only on the CPU (tests, examples). Elsewhere the
            # kernel compiles for the device or raises: never a silent
            # interpreter run on an accelerator.
            interpret = jax.default_backend() == "cpu"
        kd = jnp.dtype(krylov_dtype or jnp.float32).name
        fp, squeeze = self._forward(state, f)
        if isinstance(state, _BsrMultiState):
            # Joint recurrence over the per-shift Block-ELL matvecs (the
            # jnp reference oracle — the fused/stepwise Pallas kernels are
            # single-shift; the joint path's inner level reuses them via
            # cheb_apply's scan only in spirit, not in kernel).
            out = chebyshev.cheb_apply_joint(
                [self._bell_matvec(b, state.n_pad) for b in state.bells],
                fp,
                jnp.asarray(c, fp.dtype),
                filt.shift_lmaxes,
            )
            out = out[:, state.inv]
            return out[:, :, 0] if squeeze else out
        bell = state.bell
        tiling = autotune.select_tiling(
            state.n_pad, fp.shape[1], c.shape[0],
            bell.n_block_rows, bell.k_max, bell.block_size, fp.dtype,
            krylov_dtype=kd,
        )
        if fuse is None:
            fuse = tiling.fuse
        ft = f_tile or tiling.f_tile
        if fuse:
            out = kops.cheb_apply_bsr_fused(
                bell.blocks, bell.cols, fp, c, filt.lmax,
                interpret=interpret, f_tile=ft, krylov_dtype=kd,
            )
        else:
            out = kops.cheb_apply_bsr(
                bell.blocks, bell.cols, fp, jnp.asarray(c, fp.dtype),
                filt.lmax, interpret=interpret, f_tile=ft, krylov_dtype=kd,
            )
        out = out[:, state.inv]
        return out[:, :, 0] if squeeze else out

    @staticmethod
    def _bell_matvec(bell, n_pad: int):
        """jnp Block-ELL matvec closure handling arbitrary trailing dims."""

        def mv(v):
            flat = v.reshape(n_pad, -1)
            return kref.bsr_matvec_ref(bell, flat).reshape(v.shape)

        return mv

    def adjoint(self, filt, state, a, **_):
        # Adjoint = same recurrence on eta-stacked blocks (Sec. IV-B); the
        # matvec is the jnp Block-ELL oracle — adjoint traffic is a small
        # fraction of forward traffic, so it does not warrant a kernel.
        a = jnp.asarray(a)
        squeeze = a.ndim == 2  # (eta, N) -> signals are 1-D
        a3 = a[:, :, None] if squeeze else a
        ap = jnp.zeros((a3.shape[0], state.n_pad) + a3.shape[2:], a3.dtype)
        ap = ap.at[:, : state.n].set(a3[:, state.perm])
        if isinstance(state, _BsrMultiState):
            out = chebyshev.cheb_adjoint_apply_joint(
                [self._bell_matvec(b, state.n_pad) for b in state.bells],
                ap,
                filt.coeffs,
                filt.shift_lmaxes,
            )
        else:
            mv = self._bell_matvec(state.bell, state.n_pad)
            out = chebyshev.cheb_adjoint_apply(
                mv, ap, filt.coeffs, filt.lmax
            )
        out = out[state.inv]
        return out[:, 0] if squeeze else out

    def messages_per_apply(self, filt, state, matvec_counts) -> int:
        return 0  # single-chip: HBM traffic, not network words


class _ShardedBackendBase:
    """Shared machinery for the partition-plan distributed backends.

    ``state_key`` is shared so halo and allgather reuse one prepared
    ``DistributedGraphContext`` (the plan depends only on graph + mesh +
    axis, not on which matvec consumes it).
    """

    name = "halo"
    state_key = "partition_plan"
    # scatter_signal/gather_signal round-trip through host numpy, so these
    # backends cannot live inside a lax.scan body (traceable=False).
    capabilities = BackendCapabilities()
    prepare_opts: frozenset[str] = frozenset({"mesh", "axis", "n_parts"})

    def prepare(
        self,
        filt,
        *,
        mesh: Mesh | None = None,
        axis: str = "graph",
        n_parts: int | None = None,
        **_,
    ):
        g = _require_graph(filt, self.name)
        if mesh is None:
            mesh = _default_mesh(axis, n_parts)
        if filt.n_shifts > 1:
            # One layout from the union edge pattern, one plan per shift.
            plans = build_shift_partition_plans(
                [s.adjacency for s in filt.shifts],
                g.coords,
                mesh.shape[axis],
            )
            return MultiShiftGraphContext(
                plans=plans, mesh=mesh, axis=axis,
                lmaxes=filt.shift_lmaxes,
            )
        plan = build_partition_plan(
            g.adjacency, g.coords, mesh.shape[axis]
        )
        return DistributedGraphContext(plan=plan, mesh=mesh, axis=axis)

    def apply(self, filt, ctx, f, *, coeffs=None, overlap: bool = True, **_):
        c = _coeffs_or(filt, coeffs)
        f = jnp.asarray(f)
        squeeze = f.ndim == 1
        sharded = ctx.scatter_signal(f)
        if isinstance(ctx, MultiShiftGraphContext):
            # Joint recurrence: per-shift halo exchange inside one
            # shard_map program (serial exchange->matvec per shift; the
            # overlapped schedule remains single-shift only).
            out = ctx.cheb_apply_joint(sharded, c)
        else:
            out = ctx.cheb_apply(sharded, c, filt.lmax, backend=self.name, overlap=overlap)
        out = jnp.asarray(ctx.gather_signal(np.asarray(out)))
        return out[:, :, 0] if squeeze else out

    def adjoint(self, filt, ctx, a, **_):
        a = jnp.asarray(a)
        squeeze = a.ndim == 2
        a3 = a[:, :, None] if squeeze else a
        plan = ctx.plan
        pad = plan.n_local * plan.n_parts - plan.n
        ap = jnp.concatenate(
            [
                a3[:, plan.order],
                jnp.zeros((a3.shape[0], pad) + a3.shape[2:], a3.dtype),
            ],
            axis=1,
        )
        ap = jax.device_put(ap, NamedSharding(ctx.mesh, P(None, ctx.axis)))
        if isinstance(ctx, MultiShiftGraphContext):
            out = ctx.cheb_adjoint_joint(ap, filt.coeffs)
        else:
            out = ctx.cheb_adjoint(ap, filt.coeffs, filt.lmax)
        out = jnp.asarray(ctx.gather_signal(np.asarray(out)))
        return out[:, 0] if squeeze else out

    def messages_per_apply(self, filt, ctx, matvec_counts) -> int:
        if isinstance(ctx, MultiShiftGraphContext):
            return ctx.messages_per_apply(matvec_counts)
        return ctx.messages_per_apply(matvec_counts[0], backend=self.name)


@register_backend
class HaloBackend(_ShardedBackendBase):
    """Vertex-partitioned distributed backend, halo exchange per order.

    Algorithm 1 on the device mesh: device p sends device q exactly the
    boundary values q's Laplacian rows touch, via one ``all_to_all`` per
    recurrence order. Words per apply = ``M * halo_words <= 2 M |E|`` —
    never worse than the paper's radio bound (a boundary vertex is sent
    once per neighbouring partition, not once per edge).

    By default the overlapped schedule runs (``overlap=True`` apply
    option): each step computes its boundary rows first, issues the next
    exchange, then computes the interior rows while the collective is in
    flight. ``overlap=False`` selects the serial exchange->matvec
    reference; both move exactly the same words.

    Multi-shift filters run here too: ``prepare`` builds one partition
    plan per shift over a shared union layout
    (:func:`repro.core.distributed.build_shift_partition_plans`) and the
    joint recurrence exchanges each shift's own halo, so
    ``messages_per_apply`` becomes the per-shift sum
    ``sum_r count_r * halo_words_r``.
    """

    name = "halo"
    capabilities = BackendCapabilities(multi_shift=True)


@register_backend
class AllgatherBackend(_ShardedBackendBase):
    """Naive distributed baseline: all-gather the full signal per order.

    Words per apply = ``M * n_local * P * (P-1)`` — the §Perf "before"
    configuration that the halo backend's partition-boundary exchange
    replaces. Single-shift only (``multi_shift=False``): a baseline that
    ships whole slabs regardless of the cut has nothing per-shift to
    account, so multi-shift filters are rejected loudly at dispatch.
    """

    name = "allgather"
    capabilities = BackendCapabilities()


@dataclasses.dataclass(frozen=True)
class _GridState:
    side: int
    mesh: Mesh
    axis: str
    n_parts: int
    depth: int
    apply_fn: object  # jitted shard_map (f2, coeffs) -> (eta, N, F)
    adjoint_fn: object  # jitted shard_map (a3, coeffs) -> (N, F)


@register_backend
class GridBackend:
    """Matrix-free stencil backend for square 4-neighbour grid graphs.

    Row slabs over one mesh axis; each recurrence block exchanges a
    depth-d ghost-row halo once and runs d local steps — the
    communication-avoiding schedule (same words as per-order exchange, 1/d
    the neighbour rounds). The Laplacian is never materialized: at 10^5+
    vertices this is the production configuration (DESIGN.md Sec. 6.2).

    Options: ``mesh`` / ``axis`` / ``n_parts`` (prepare), ``depth``
    (prepare; ghost depth d, default 2 capped to rows-per-slab).
    """

    name = "grid"
    # apply/adjoint place inputs with device_put before entering the jitted
    # shard_map program — a host-side staging step; keep it out of scan
    # (traceable=False). Single-shift only: the stencil IS the shift.
    capabilities = BackendCapabilities()
    prepare_opts: frozenset[str] = frozenset(
        {"mesh", "axis", "n_parts", "depth"}
    )

    def prepare(
        self,
        filt,
        *,
        mesh: Mesh | None = None,
        axis: str = "grid",
        n_parts: int | None = None,
        depth: int = 2,
        **_,
    ):
        g = _require_graph(filt, self.name)
        n = g.n_vertices
        side = int(round(math.sqrt(n)))
        if side * side != n:
            raise ValueError(
                f"grid backend needs a square grid graph, got N={n}"
            )
        # Structural validation at every scale: unit weights, the stencil
        # degree field, and the exact edge count together pin down the
        # 4-neighbour grid without building a reference adjacency.
        a = np.asarray(g.adjacency)
        vals = np.unique(a)
        deg = a.sum(axis=1).reshape(side, side)
        want_deg = np.full((side, side), 4.0)
        want_deg[0, :] -= 1.0
        want_deg[-1, :] -= 1.0
        want_deg[:, 0] -= 1.0
        want_deg[:, -1] -= 1.0
        n_edges_want = 2 * side * (side - 1)
        if (not np.all(np.isin(vals, (0.0, 1.0)))
                or not np.array_equal(deg, want_deg)
                or int(np.count_nonzero(a)) != 2 * n_edges_want):
            raise ValueError(
                "grid backend: adjacency is not the unit-weight "
                f"4-neighbour {side}x{side} grid"
            )
        if n <= 4096:  # exact check is cheap at test scales
            want = np.asarray(graph_lib.grid_graph(side).adjacency)
            if not np.array_equal(a, want):
                raise ValueError(
                    "grid backend: adjacency is not the unit-weight "
                    f"4-neighbour {side}x{side} grid"
                )
        if mesh is None:
            mesh = _default_mesh(axis, n_parts)
        p = mesh.shape[axis]
        if side % p != 0:
            raise ValueError(f"side={side} not divisible by n_parts={p}")
        depth = max(1, min(depth, side // p))
        lmax = filt.lmax

        # Build the jitted shard_map programs once per prepared state —
        # coefficients enter as a (replicated) argument so the same
        # compiled program serves apply() and gram().
        def local_apply(f_loc, c):
            return grid_cheb_apply_ca(
                f_loc, jnp.asarray(c, f_loc.dtype), lmax,
                side=side, axis_names=(axis,), n_parts=p, depth=depth,
            )

        apply_fn = jax.jit(shard_map(
            local_apply, mesh=mesh,
            in_specs=(P(axis), P(None, None)),
            out_specs=P(None, axis),
        ))

        def local_adjoint(a_loc, c):
            def mv(v):  # (n_local, [F,] eta) — flatten for the stencil
                flat = v.reshape(v.shape[0], -1)
                out = grid_slab_matvec(flat, side=side, axis_names=(axis,), n_parts=p)
                return out.reshape(v.shape)

            return chebyshev.cheb_adjoint_apply(mv, a_loc, jnp.asarray(c, a_loc.dtype), lmax)

        adjoint_fn = jax.jit(
            shard_map(
                local_adjoint,
                mesh=mesh,
                in_specs=(P(None, axis), P(None, None)),
                out_specs=P(axis),
            )
        )

        return _GridState(
            side=side,
            mesh=mesh,
            axis=axis,
            n_parts=p,
            depth=depth,
            apply_fn=apply_fn,
            adjoint_fn=adjoint_fn,
        )

    def apply(self, filt, state: _GridState, f, *, coeffs=None, **_):
        c = jnp.asarray(_coeffs_or(filt, coeffs), jnp.float32)
        f = jnp.asarray(f)
        squeeze = f.ndim == 1
        f2 = f[:, None] if squeeze else f
        f2 = jax.device_put(f2, NamedSharding(state.mesh, P(state.axis)))
        out = state.apply_fn(f2, c)
        return out[:, :, 0] if squeeze else out

    def adjoint(self, filt, state: _GridState, a, **_):
        a = jnp.asarray(a)
        squeeze = a.ndim == 2
        a3 = a[:, :, None] if squeeze else a
        a3 = jax.device_put(a3, NamedSharding(state.mesh, P(None, state.axis)))
        out = state.adjoint_fn(a3, jnp.asarray(filt.coeffs, jnp.float32))
        return out[:, 0] if squeeze else out

    def messages_per_apply(self, filt, state: _GridState, matvec_counts) -> int:
        # one (side,) boundary row up + down per order across P-1 seams;
        # the CA schedule moves the same words in order/depth rounds.
        return matvec_counts[0] * 2 * (state.n_parts - 1) * state.side
