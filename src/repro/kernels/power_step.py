"""Fused Power-ψ iteration kernel: scatter + epilogue + gap in one pass.

One Alg. 2 step is ``s' = μ ⊙ (sᵀA-push) + c`` followed by the termination
gap ``‖s' − s‖₁``. Unfused, that is three extra O(N) HBM sweeps after the
scatter (scale, add, abs-diff-reduce). This kernel fuses them into the edge
scatter's epilogue: when the *last* edge block of a node tile completes, the
tile's μ/c/s slices are already in VMEM, the epilogue runs there, and a
per-kernel scalar accumulates the L1 gap — so s', and the gap cost zero
additional HBM traffic beyond the write of s' itself.

The per-edge gather ``(s ⊙ 1/w)[src]`` runs before this kernel and arrives
as ``[e1, e2]`` blocks: in the ``psi_gather`` kernel (``kernels/gather.py``),
which holds the source vector in VMEM, where it fits, and in XLA otherwise
(``kernels/ops.py`` ``gather_step_edges``). This kernel itself keeps no
whole-vector block in VMEM.

The three per-block tables (``block_tile``, ``block_first``,
``block_last``) are scalar-prefetched into SMEM, 12 bytes a block. Where a
format has more blocks than :data:`SMEM_TABLE_BUDGET` holds, one step is
several calls over tile-aligned block ranges (:func:`call_starts`), so that
no node tile's blocks straddle two calls. The first call allocates s′ and
writes its own tiles; every later call takes s′ and the gap so far as a
carry, writes its tiles of s′ in place (``input_output_aliases``) and goes
on adding to the gap, so the split step's s′ and gap equal one call's bit
for bit and nothing O(N) is concatenated.

This is the paper-faithful iteration (identical math to
``core.power_psi.make_power_psi_step``) — only the schedule is new
(EXPERIMENTS.md §Perf, memory-term hillclimb).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["SMEM_TABLE_BUDGET", "call_starts", "power_step_call"]

# SMEM bytes one call's scalar-prefetch tables may take: v5e has 1 MiB of
# SMEM per core, and a quarter of it is left to Mosaic's own scalars, so a
# call runs at most 65,536 blocks
SMEM_TABLE_BUDGET = 768 * 1024
_TABLE_BYTES = 3 * 4     # block_tile, block_first, block_last: int32 each


def call_starts(block_tile: np.ndarray) -> tuple[int, ...]:
    """The blocks at which the second and later calls of one step start:
    each at a node tile's first block, each call within
    :data:`SMEM_TABLE_BUDGET`. Empty where one call holds every block."""
    cap = SMEM_TABLE_BUDGET // _TABLE_BYTES
    num_blocks = len(block_tile)
    tile_starts = np.flatnonzero(np.diff(block_tile, prepend=-1))
    starts, at = [], 0
    while num_blocks - at > cap:
        nxt = int(tile_starts[np.searchsorted(tile_starts, at + cap,
                                              side="right") - 1])
        if nxt <= at:
            raise ValueError(f"node tile at block {at} spans more than "
                             f"{cap} blocks, one call's SMEM tables")
        starts.append(nxt)
        at = nxt
    return tuple(starts)


def _make_kernel(e1: int, tile: int, chained: bool):
    def kernel(block_tile_ref, first_ref, last_ref, vals_ref, dstl_ref,
               mu_ref, c_ref, s_old_ref, *refs):
        if chained:     # s' of the calls before (aliased, never read)
            _, gap_in_ref, out_ref, gap_ref, acc_ref = refs
        else:
            out_ref, gap_ref, acc_ref = refs
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _init_gap():
            gap_ref[...] = (gap_in_ref[...] if chained
                            else jnp.zeros_like(gap_ref))

        @pl.when(first_ref[b] == 1)
        def _zero_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        vals = vals_ref[0]                                # [e1, e2]
        dstl = dstl_ref[0]
        e2 = vals.shape[1]
        acc = acc_ref[...]
        for r in range(e1):
            onehot = (dstl[r][:, None] ==
                      jax.lax.broadcasted_iota(jnp.int32, (e2, tile), 1)
                      ).astype(vals.dtype)
            acc = acc + jnp.dot(vals[r][None, :], onehot,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=vals.dtype)
        acc_ref[...] = acc

        @pl.when(last_ref[b] == 1)
        def _epilogue():
            s_new = mu_ref[...] * acc_ref[...] + c_ref[...]   # [1, tile]
            out_ref[...] = s_new
            # Mosaic stores vectors only: keep the reduction [1, 1]
            gap_ref[...] += jnp.sum(jnp.abs(s_new - s_old_ref[...]),
                                    keepdims=True)

    return kernel


@functools.partial(jax.jit, static_argnames=("tile", "e1", "e2", "num_tiles",
                                             "first_block", "interpret"))
def power_step_call(edge_vals: jax.Array, dst_local: jax.Array,
                    block_tile: jax.Array, block_first: jax.Array,
                    block_last: jax.Array, mu_pad: jax.Array,
                    c_pad: jax.Array, s_old_pad: jax.Array,
                    carry: tuple[jax.Array, jax.Array] | None = None,
                    *, tile: int, e1: int, e2: int, num_tiles: int,
                    first_block: int = 0,
                    interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Fused iteration over a pre-built EdgeTileFormat, or over the
    tile-aligned block range of it that starts at ``first_block``.

    Args:
      edge_vals: f[num_blocks, e1, e2] — ``(s ⊙ 1/w)[src_idx]``, gathered
        before the call (sentinel slots hold 0); ``dst_local`` alike. Both
        whole: the call reads its blocks from ``first_block`` on.
      block_tile / block_first / block_last: i32 tables of the call's own
        blocks, scalar-prefetched into SMEM.
      mu_pad / c_pad / s_old_pad: f[1, num_tiles*tile] node-tiled vectors.
      carry: None for a step's first call; for a later one, ``(s_new,
        gap)`` of the calls before it, updated in place.

    Returns:
      (s_new f[1, num_tiles*tile], gap f[1,1] = ‖s_new − s_old‖₁ over pads),
      as far as this call's tiles and the calls before it go.
    """
    num_blocks = block_tile.shape[0]
    chained = carry is not None

    def edge_block(b, *_):
        return (b + first_block if first_block else b, 0, 0)

    vec_spec = pl.BlockSpec((1, tile), lambda b, bt, bf, bl: (0, bt[b]))
    gap_spec = pl.BlockSpec((1, 1), lambda b, *_: (0, 0))
    in_specs = [
        pl.BlockSpec((1, e1, e2), edge_block),
        pl.BlockSpec((1, e1, e2), edge_block),
        vec_spec,                                   # mu
        vec_spec,                                   # c
        vec_spec,                                   # s_old
    ]
    if chained:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY), gap_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_blocks,),
        in_specs=in_specs,
        out_specs=[
            vec_spec,                                   # s_new
            gap_spec,                                   # gap scalar
        ],
        scratch_shapes=[pltpu.VMEM((1, tile), edge_vals.dtype)],
    )
    return pl.pallas_call(
        _make_kernel(e1, tile, chained),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, num_tiles * tile), edge_vals.dtype),
            jax.ShapeDtypeStruct((1, 1), edge_vals.dtype),
        ],
        # s_new is operand 8: the three tables, then five blocked inputs
        input_output_aliases={8: 0} if chained else {},
        interpret=interpret,
        name="psi_power_step",
    )(block_tile, block_first, block_last, edge_vals, dst_local,
      mu_pad, c_pad, s_old_pad, *(carry or ()))
