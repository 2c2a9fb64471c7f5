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

This is the paper-faithful iteration (identical math to
``core.power_psi.make_power_psi_step``) — only the schedule is new
(EXPERIMENTS.md §Perf, memory-term hillclimb).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["power_step_call"]


def _make_kernel(e1: int, tile: int):
    def kernel(block_tile_ref, first_ref, last_ref, vals_ref, dstl_ref,
               mu_ref, c_ref, s_old_ref, out_ref, gap_ref, acc_ref):
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _zero_gap():
            gap_ref[...] = jnp.zeros_like(gap_ref)

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
                                             "interpret"))
def power_step_call(edge_vals: jax.Array, dst_local: jax.Array,
                    block_tile: jax.Array, block_first: jax.Array,
                    block_last: jax.Array, mu_pad: jax.Array,
                    c_pad: jax.Array, s_old_pad: jax.Array,
                    *, tile: int, e1: int, e2: int, num_tiles: int,
                    interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Fused iteration over a pre-built EdgeTileFormat.

    Args:
      edge_vals: f[num_blocks, e1, e2] — ``(s ⊙ 1/w)[src_idx]``, gathered
        before the call (sentinel slots hold 0).
      mu_pad / c_pad / s_old_pad: f[1, num_tiles*tile] node-tiled vectors.

    Returns:
      (s_new f[1, num_tiles*tile], gap f[1,1] = ‖s_new − s_old‖₁ over pads).
    """
    num_blocks = edge_vals.shape[0]
    vec_spec = pl.BlockSpec((1, tile), lambda b, bt, bf, bl: (0, bt[b]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((1, e1, e2), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, e1, e2), lambda b, *_: (b, 0, 0)),
            vec_spec,                                   # mu
            vec_spec,                                   # c
            vec_spec,                                   # s_old
        ],
        out_specs=[
            vec_spec,                                   # s_new
            pl.BlockSpec((1, 1), lambda b, *_: (0, 0)),  # gap scalar
        ],
        scratch_shapes=[pltpu.VMEM((1, tile), edge_vals.dtype)],
    )
    return pl.pallas_call(
        _make_kernel(e1, tile),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, num_tiles * tile), edge_vals.dtype),
            jax.ShapeDtypeStruct((1, 1), edge_vals.dtype),
        ],
        interpret=interpret,
        name="psi_power_step",
    )(block_tile, block_first, block_last, edge_vals, dst_local,
      mu_pad, c_pad, s_old_pad)
