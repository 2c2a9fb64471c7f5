"""Distributed Power-ψ: shard_map over the production mesh (DESIGN.md §4).

One iteration on the (pod ×) data × model mesh:

  1. local push       — gather s·(1/w) by local src ids, sorted segment-sum
                        onto the local dst block                 [compute]
  2. psum_scatter     — reduce partials over the src axis; the scattered
                        slice IS piece (r, c) of the block-cyclic src layout
                        (zero on-device reshuffling)            [collective]
  3. epilogue         — s'_piece = μ_piece ⊙ t_piece + c_piece   [compute]
  4. all_gather       — over the model axis: row r reassembles its full
                        block-cyclic shard of s'                [collective]
  5. gap              — local L1 of Δs, psum over the src axis   [scalar]

Per-device comm per iteration: Nc floats reduced + N/d gathered — the
bandwidth-optimal 2-D SpMV schedule. The multi-pod mesh folds "pod" into the
src axis, so step 2's reduction is hierarchical (intra-pod ICI first,
inter-pod DCI second) under XLA's multi-axis psum.

Fault tolerance: s is the *entire* algorithm state (a few MB), checkpointed
every ``ckpt_every`` outer chunks by the driver in ``runtime/``; restart
warm-starts the contraction exactly (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graphs.partition import Partition2D, partition_2d
from ..graphs.structure import Graph
from .activity import Activity

__all__ = ["DistributedPsi", "DistPsiArrays", "PartialReduction",
           "BlockOverflowError"]


class BlockOverflowError(RuntimeError):
    """An edge insert does not fit a partition block's ``e_max`` capacity.

    Carries which (row, col) block overflowed and the capacity the insert
    would need, so callers can regrow the partition deliberately instead of
    guessing from a silent failure.
    """

    def __init__(self, block: tuple[int, int], e_max: int, required: int):
        self.block = block
        self.e_max = e_max
        self.required = required
        super().__init__(
            f"distributed edge block (row={block[0]}, col={block[1]}) "
            f"overflows e_max={e_max}: the insert requires capacity "
            f">= {required}; regrow the partition (re-prepare) or construct "
            f"the engine with on_overflow='regrow'")


@dataclasses.dataclass(frozen=True)
class PartialReduction:
    """Explicit handle between the dispatch and finalize halves of one
    sharded iteration: the un-psummed per-device dst partials plus the
    iterate they were pushed from (the finalize half needs it for the gap).

    Produced by :meth:`DistributedPsi.make_dispatch`, consumed by
    :meth:`DistributedPsi.make_finalize`; composing the two is bit-identical
    to the fused :meth:`DistributedPsi.make_step` program. The split exists
    so an overlapped executor can issue the next dispatch (pure local
    compute) while a previous finalize (the collective half) is still in
    flight.
    """

    partial_t: jax.Array   # f[d, mo, nc] — pre-reduction dst partials
    s_in: jax.Array        # f[d, local]  — src-layout iterate the push read


jax.tree_util.register_dataclass(
    PartialReduction, data_fields=["partial_t", "s_in"], meta_fields=[])


@dataclasses.dataclass(frozen=True)
class DistPsiArrays:
    """Device arrays for the sharded iteration (a pytree)."""
    src_local: jax.Array   # i32[d, mo, e_max]
    dst_local: jax.Array   # i32[d, mo, e_max]
    inv_w_src: jax.Array   # f[d, mo·q]   block-cyclic src layout
    mu_piece: jax.Array    # f[d, mo, q]
    c_piece: jax.Array     # f[d, mo, q]
    c_src: jax.Array       # f[d, mo·q]   s₀ in src layout
    lam_piece: jax.Array   # f[d, mo, q]  for the ψ epilogue
    d_piece: jax.Array     # f[d, mo, q]


jax.tree_util.register_dataclass(
    DistPsiArrays,
    data_fields=["src_local", "dst_local", "inv_w_src", "mu_piece",
                 "c_piece", "c_src", "lam_piece", "d_piece"],
    meta_fields=[])


class DistributedPsi:
    """Power-ψ sharded over a ("data","model") or ("pod","data","model") mesh."""

    def __init__(self, part: Partition2D, mesh: Mesh, *, dtype=jnp.float32,
                 arrays: DistPsiArrays | None = None):
        self.part = part
        self.mesh = mesh
        self.dtype = dtype
        axes = mesh.axis_names
        if axes[-2:] != ("data", "model"):
            raise ValueError(f"mesh must end in (data, model); got {axes}")
        self.src_axes = axes[:-1]        # ("data",) or ("pod","data")
        d_mesh = int(np.prod([mesh.shape[a] for a in self.src_axes]))
        if d_mesh != part.d or mesh.shape["model"] != part.mo:
            raise ValueError("partition grid does not match mesh shape")
        self.arrays = arrays

    # ------------------------------------------------------------------ #
    @classmethod
    def from_graph(cls, graph: Graph, activity: Activity, mesh: Mesh, *,
                   dtype=jnp.float32) -> "DistributedPsi":
        axes = mesh.axis_names
        d = int(np.prod([mesh.shape[a] for a in axes[:-1]]))
        part = partition_2d(graph, d, mesh.shape["model"])
        self = cls(part, mesh, dtype=dtype)
        self.arrays = self.build_arrays(graph, activity)
        return self

    def build_arrays(self, graph: Graph, activity: Activity) -> DistPsiArrays:
        """Host-side operator build in partitioned layouts → device."""
        p = self.part
        np_dtype = np.dtype(jnp.dtype(self.dtype).name)
        lam = activity.lam.astype(np_dtype)
        mu = activity.mu.astype(np_dtype)
        total = lam + mu
        w = np.zeros(graph.n, np_dtype)
        np.add.at(w, graph.src, total[graph.dst])
        inv_w = np.where(w > 0, 1.0 / np.where(w > 0, w, 1), 0).astype(np_dtype)
        with np.errstate(divide="ignore", invalid="ignore"):
            c = np.where(total > 0, mu / total, 0.0).astype(np_dtype)
            dd = np.where(total > 0, lam / total, 0.0).astype(np_dtype)

        put = partial(self._put)
        return DistPsiArrays(
            src_local=put(p.src_local, P(self.src_axes, "model")),
            dst_local=put(p.dst_local, P(self.src_axes, "model")),
            inv_w_src=put(p.to_src_layout(inv_w), P(self.src_axes)),
            mu_piece=put(p.to_piece_layout(mu), P(self.src_axes, "model")),
            c_piece=put(p.to_piece_layout(c), P(self.src_axes, "model")),
            c_src=put(p.to_src_layout(c), P(self.src_axes)),
            lam_piece=put(p.to_piece_layout(lam), P(self.src_axes, "model")),
            d_piece=put(p.to_piece_layout(dd), P(self.src_axes, "model")),
        )

    def _put(self, host: np.ndarray, spec: P) -> jax.Array:
        # leading host dim(s) split over the named axes; trailing dims local
        full_spec = P(*spec, *([None] * (host.ndim - len(spec))))
        return jax.device_put(
            host, NamedSharding(self.mesh, full_spec))

    # ------------------------------------------------------------------ #
    def input_specs(self):
        """ShapeDtypeStructs for the dry-run (no allocation)."""
        p = self.part
        e = p.e_max
        sd = jax.ShapeDtypeStruct
        i32, f = jnp.int32, self.dtype
        return dict(
            src_local=sd((p.d, p.mo, e), i32),
            dst_local=sd((p.d, p.mo, e), i32),
            inv_w_src=sd((p.d, p.mo * p.q), f),
            mu_piece=sd((p.d, p.mo, p.q), f),
            c_piece=sd((p.d, p.mo, p.q), f),
            c_src=sd((p.d, p.mo * p.q), f),
            lam_piece=sd((p.d, p.mo, p.q), f),
            d_piece=sd((p.d, p.mo, p.q), f),
        )

    def shardings(self):
        src_axes = self.src_axes
        row = NamedSharding(self.mesh, P(src_axes, None))
        grid = NamedSharding(self.mesh, P(src_axes, "model", None))
        return dict(src_local=grid, dst_local=grid, inv_w_src=row,
                    mu_piece=grid, c_piece=grid, c_src=row,
                    lam_piece=grid, d_piece=grid)

    # ------------------------------------------------------------------ #
    def _arr_specs(self) -> DistPsiArrays:
        """Partition specs of the array pytree inside every shard_map."""
        src_axes = self.src_axes
        grid = P(src_axes, "model", None)
        row = P(src_axes, None)
        return DistPsiArrays(
            src_local=grid, dst_local=grid, inv_w_src=row, mu_piece=grid,
            c_piece=grid, c_src=row, lam_piece=grid, d_piece=grid)

    @staticmethod
    def _local_push(s, a: DistPsiArrays, nc: int) -> jax.Array:
        """Dispatch half's local math (inside shard_map, shapes [1, ...]):
        gather s·(1/w) by local src ids, sorted segment-sum onto the local
        dst block. Pure compute — no collectives."""
        s_loc = s[0]
        src_ids = a.src_local[0, 0]
        dst_ids = a.dst_local[0, 0]
        s_pre = jnp.concatenate(
            [s_loc * a.inv_w_src[0], jnp.zeros((1,), s.dtype)])
        return jax.ops.segment_sum(
            s_pre[src_ids], dst_ids, nc + 1, indices_are_sorted=True)[:nc]

    @staticmethod
    def _local_finish(partial_t, s, a: DistPsiArrays, src_axes):
        """Finalize half's local math: psum_scatter the partials (the
        scattered slice IS piece (r, c)), μ/c epilogue, all_gather over the
        model axis, psummed l1 gap against the input iterate."""
        t_piece = jax.lax.psum_scatter(
            partial_t, src_axes, scatter_dimension=0, tiled=True)
        s_new_piece = a.mu_piece[0, 0] * t_piece + a.c_piece[0, 0]
        s_new = jax.lax.all_gather(
            s_new_piece, "model", axis=0, tiled=True)[None]
        gap_local = jnp.sum(jnp.abs(s_new - s))
        gap = jax.lax.psum(gap_local, src_axes)
        return s_new, gap

    def make_step(self):
        """shard_map'd single iteration: (s_src, arrays) → (s'_src, gap).

        The fused composition of :meth:`make_dispatch` and
        :meth:`make_finalize` in one program (XLA overlaps the next tile's
        gather with the previous collective where it can); the split halves
        below expose the same math with an explicit
        :class:`PartialReduction` boundary for overlapped executors.
        """
        src_axes = self.src_axes
        nc = self.part.nc

        def local_step(s, a: DistPsiArrays):
            partial_t = self._local_push(s, a, nc)
            return self._local_finish(partial_t, s, a, src_axes)

        return jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(P(src_axes, None), self._arr_specs()),
            out_specs=(P(src_axes, None), P()), check_vma=False)

    def make_dispatch(self):
        """Compute-only half: (s_src, arrays) → :class:`PartialReduction`.

        No collectives are issued — the returned handle carries the
        un-psummed per-device dst partials (and the iterate, for the
        finalize gap), so a scheduler can dispatch the *next* chunk's local
        push before this handle's reduction has drained.
        """
        src_axes = self.src_axes
        nc = self.part.nc

        def local_dispatch(s, a: DistPsiArrays):
            partial_t = self._local_push(s, a, nc)
            return PartialReduction(partial_t=partial_t[None, None], s_in=s)

        return jax.shard_map(
            local_dispatch, mesh=self.mesh,
            in_specs=(P(src_axes, None), self._arr_specs()),
            out_specs=PartialReduction(
                partial_t=P(src_axes, "model", None),
                s_in=P(src_axes, None)), check_vma=False)

    def make_finalize(self):
        """Collective half: (:class:`PartialReduction`, arrays) →
        (s'_src, gap). psum_scatter + epilogue + all_gather + gap psum —
        exactly the tail of :meth:`make_step`."""
        src_axes = self.src_axes

        def local_finalize(h: PartialReduction, a: DistPsiArrays):
            return self._local_finish(h.partial_t[0, 0], h.s_in, a, src_axes)

        return jax.shard_map(
            local_finalize, mesh=self.mesh,
            in_specs=(PartialReduction(
                partial_t=P(src_axes, "model", None),
                s_in=P(src_axes, None)), self._arr_specs()),
            out_specs=(P(src_axes, None), P()), check_vma=False)

    def make_epilogue(self):
        """ψ from converged s: one more push, then (λ⊙t + d)/N, dst layout."""
        src_axes = self.src_axes
        nc, n = self.part.nc, self.part.n

        def local_epilogue(s, a: DistPsiArrays):
            partial_t = self._local_push(s, a, nc)
            t_piece = jax.lax.psum_scatter(
                partial_t, src_axes, scatter_dimension=0, tiled=True)
            psi_piece = (a.lam_piece[0, 0] * t_piece + a.d_piece[0, 0]) / n
            return psi_piece[None, None]

        return jax.shard_map(
            local_epilogue, mesh=self.mesh,
            in_specs=(P(src_axes, None), self._arr_specs()),
            out_specs=P(src_axes, "model", None), check_vma=False)

    # ------------------------------------------------------------------ #
    def make_run(self, *, chunk_iters: int = 8, unroll: bool = False):
        """(s, arrays) → (s', gap): ``chunk_iters`` fused steps + final gap.

        The driver loops chunks until gap ≤ tol, checkpointing s between
        chunks (runtime/psi_driver.py); keeping the while on the host makes
        the device program a fixed-shape scan — required for the dry-run and
        friendlier to multi-pod SPMD.
        """
        step = self.make_step()

        @jax.jit
        def run(s, arrays):
            def body(carry, _):
                s, _ = carry
                s_new, gap = step(s, arrays)
                return (s_new, gap), None

            (s_fin, gap), _ = jax.lax.scan(
                body, (s, jnp.asarray(jnp.inf, s.dtype)), None,
                length=chunk_iters, unroll=chunk_iters if unroll else 1)
            return s_fin, gap

        return run

    def run_to_convergence(self, *, tol: float = 1e-9, max_iter: int = 2000,
                           chunk_iters: int = 16, b_norm: float | None = None):
        """Host-driven convergence loop. Returns (psi [n], iters, gap)."""
        if self.arrays is None:
            raise ValueError("no device arrays; use from_graph()")
        run = self.make_run(chunk_iters=chunk_iters)
        epi = jax.jit(self.make_epilogue())
        s = self.arrays.c_src
        scale = 1.0 if b_norm is None else b_norm
        it = 0
        gap = np.inf
        while it < max_iter:
            s, gap_dev = run(s, self.arrays)
            it += chunk_iters
            gap = float(gap_dev) * scale
            if gap <= tol:
                break
        psi_piece = epi(s, self.arrays)          # [d, mo, q] dst-piece layout
        psi = self.part.from_src_layout(
            np.asarray(psi_piece).reshape(self.part.d, -1))
        return psi, it, gap


class DistributedPsi1D:
    """Paper-faithful distributed baseline (§III: 'can even be calculated
    distributedly'): edges sharded across all devices, s **replicated**,
    one full-vector psum per iteration.

    This is the natural 1-D reading of the paper's distribution remark.
    EXPERIMENTS.md §Perf compares it against the 2-D block-cyclic schedule
    (DistributedPsi): the 1-D psum moves ~2·N·4 B per device per iteration
    versus the 2-D scheme's Nc·4 (reduce-scatter) + N/d·4 (all-gather) —
    a ~2·min(d, mo)× collective reduction at equal math.
    """

    def __init__(self, graph: Graph, activity: Activity, mesh: Mesh, *,
                 dtype=jnp.float32, spec_only: bool = False,
                 n: int | None = None, m: int | None = None):
        self.mesh = mesh
        self.dtype = dtype
        self.axes = tuple(mesh.axis_names)
        self.n_dev = int(np.prod([mesh.shape[a] for a in self.axes]))
        if spec_only:
            self.n = n
            self.n_pad = -(-n // 128) * 128
            self.e_max = -(-int(np.ceil(m / self.n_dev * 1.3)) // 128) * 128
            self.arrays = None
            return
        self.n = graph.n
        self.n_pad = -(-graph.n // 128) * 128
        np_dtype = np.dtype(jnp.dtype(dtype).name)
        act_l = activity.lam.astype(np_dtype)
        act_m = activity.mu.astype(np_dtype)
        total = act_l + act_m
        w = np.zeros(graph.n, np_dtype)
        np.add.at(w, graph.src, total[graph.dst])
        inv_w = np.where(w > 0, 1.0 / np.where(w > 0, w, 1), 0)
        pad = lambda v: np.concatenate(
            [v.astype(np_dtype), np.zeros(self.n_pad - graph.n, np_dtype)])
        with np.errstate(divide="ignore", invalid="ignore"):
            c = np.where(total > 0, act_m / total, 0.0)
        # edges round-robin over devices, dst-sorted within each shard
        src, dst = graph.edges_by_dst
        per = -(-graph.m // self.n_dev)
        self.e_max = -(-per // 128) * 128
        es = np.full((self.n_dev, self.e_max), self.n_pad, np.int32)
        ed = np.full((self.n_dev, self.e_max), self.n_pad, np.int32)
        for i in range(self.n_dev):
            sl = slice(i * per, min((i + 1) * per, graph.m))
            k = sl.stop - sl.start
            es[i, :k] = src[sl]
            ed[i, :k] = dst[sl]
        flat = P(self.axes)
        self.arrays = dict(
            src=jax.device_put(es.reshape(self.n_dev, self.e_max),
                               NamedSharding(mesh, P(self.axes, None))),
            dst=jax.device_put(ed.reshape(self.n_dev, self.e_max),
                               NamedSharding(mesh, P(self.axes, None))),
            inv_w=jax.device_put(pad(inv_w), NamedSharding(mesh, P())),
            mu=jax.device_put(pad(act_m), NamedSharding(mesh, P())),
            c=jax.device_put(pad(c), NamedSharding(mesh, P())))

    def make_step(self):
        n_pad = self.n_pad
        axes = self.axes

        def local_step(s, src, dst, inv_w, mu, c):
            s_pre = jnp.concatenate(
                [s * inv_w, jnp.zeros((1,), s.dtype)])
            partial = jax.ops.segment_sum(
                s_pre[src[0]], dst[0], n_pad + 1,
                indices_are_sorted=True)[:n_pad]
            t = jax.lax.psum(partial, axes)            # full-vector AR
            return mu * t + c
        # NOTE: the convergence gap is computed by the caller from
        # (s_new, s_old) — returning a replicated scalar second output from
        # this shard_map deadlocks the XLA CPU in-process communicator
        # (runtime quirk; compile is fine either way).

        return jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(P(), P(self.axes, None), P(self.axes, None),
                      P(), P(), P()),
            out_specs=P(), check_vma=False)

    def input_specs(self):
        sd = jax.ShapeDtypeStruct
        return dict(
            s=sd((self.n_pad,), self.dtype),
            src=sd((self.n_dev, self.e_max), jnp.int32),
            dst=sd((self.n_dev, self.e_max), jnp.int32),
            inv_w=sd((self.n_pad,), self.dtype),
            mu=sd((self.n_pad,), self.dtype),
            c=sd((self.n_pad,), self.dtype))

    def shardings(self):
        e = NamedSharding(self.mesh, P(self.axes, None))
        r = NamedSharding(self.mesh, P())
        return dict(s=r, src=e, dst=e, inv_w=r, mu=r, c=r)
