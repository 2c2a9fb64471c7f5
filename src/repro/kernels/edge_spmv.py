"""Edge-tile SpMV Pallas kernel — the ψ-score push as one-hot MXU matmuls.

TPU-native design (DESIGN.md §3): edges are pre-blocked so each block of
``e1 × e2`` edges writes a single output tile of ``tile`` nodes. Per block:

  1. gather ``s_pre[src_idx]`` (× optional per-edge weights) — XLA, before
     the kernel; Mosaic lowers only 2-D gathers, and a gathered block keeps
     VMEM independent of N
  2. scatter-by-one-hot                 — e1 × ([1, e2] @ [e2, tile]) MXU
                                          mat-vecs accumulated into the
                                          output tile resident in VMEM, at
                                          f32 contract precision (the
                                          values are not rounded to bf16)

The output BlockSpec revisits the same tile for consecutive blocks of one
node tile (grid is ordered dst-major), so accumulation happens in VMEM and
each output tile is written to HBM exactly once. VMEM footprint per step:
2·e1·e2 values/indices + tile f32, sized for v5e VMEM with 128-lane /
8-sublane alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["edge_spmv_call"]


def _make_kernel(e1: int, tile: int):
    def kernel(block_tile_ref, first_ref, vals_ref, dstl_ref, out_ref):
        b = pl.program_id(0)

        @pl.when(first_ref[b] == 1)
        def _zero():
            out_ref[...] = jnp.zeros_like(out_ref)

        vals = vals_ref[0]                                # [e1, e2]
        dstl = dstl_ref[0]                                # [e1, e2] i32
        e2 = vals.shape[1]
        acc = out_ref[...]                                # [1, tile]
        for r in range(e1):                               # static unroll
            onehot = (dstl[r][:, None] ==
                      jax.lax.broadcasted_iota(jnp.int32, (e2, tile), 1)
                      ).astype(vals.dtype)                # [e2, tile]
            acc = acc + jnp.dot(vals[r][None, :], onehot,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=vals.dtype)
        out_ref[...] = acc

    return kernel


@functools.partial(jax.jit, static_argnames=("tile", "e1", "e2", "num_tiles",
                                             "interpret"))
def edge_spmv_call(edge_vals: jax.Array, dst_local: jax.Array,
                   block_tile: jax.Array, block_first: jax.Array,
                   *, tile: int, e1: int, e2: int, num_tiles: int,
                   interpret: bool = False) -> jax.Array:
    """Raw pallas_call over a pre-built EdgeTileFormat (arrays on device).

    Args:
      edge_vals: f[num_blocks, e1, e2] per-slot contributions (the gathered
        ``s_pre[src_idx]``, already weighted); sentinel slots hold 0.
      dst_local: i32[num_blocks, e1, e2].
      block_tile / block_first: i32[num_blocks] scalar-prefetch tables.

    Returns:
      f[1, num_tiles * tile] scatter result; caller slices [:, :n].
    """
    num_blocks = edge_vals.shape[0]
    block = pl.BlockSpec((1, e1, e2), lambda b, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_blocks,),
        in_specs=[block, block],
        out_specs=pl.BlockSpec((1, tile), lambda b, bt, bf: (0, bt[b])),
    )
    return pl.pallas_call(
        _make_kernel(e1, tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, num_tiles * tile),
                                       edge_vals.dtype),
        interpret=interpret,
    )(block_tile, block_first, edge_vals, dst_local)
