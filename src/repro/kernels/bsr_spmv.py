"""Block-sparse-row SpMV Pallas kernel — dense MXU tiles with a block table.

The MXU-regime alternative to ``edge_spmv``: A is cut into dense ts×td tiles,
only non-empty tiles are stored, and a scalar-prefetch block table drives the
BlockSpec index maps (the PagedAttention indirection pattern):

  out[dst_tile]  +=  s_pre[src_tile] @ tiles[b]        # [1,ts] @ [ts,td] MXU

Grid order is dst-major so each output tile stays resident in VMEM across
its inner accumulation. For hyper-sparse social graphs tile occupancy is
poor (EXPERIMENTS.md §Perf quantifies it); the kernel exists as the honest
MXU baseline and wins on clustered/banded operators.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bsr_spmv_call"]


def _kernel(src_tile_ref, dst_tile_ref, first_ref, s_ref, tiles_ref, out_ref):
    b = pl.program_id(0)

    @pl.when(first_ref[b] == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(s_ref[...], tiles_ref[0],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ts", "td", "num_dst_tiles",
                                             "interpret"))
def bsr_spmv_call(s_pre_pad: jax.Array, tiles: jax.Array,
                  src_tile: jax.Array, dst_tile: jax.Array,
                  block_first: jax.Array, *, ts: int, td: int,
                  num_dst_tiles: int, interpret: bool = False) -> jax.Array:
    """Raw pallas_call over a pre-built BsrFormat.

    Args:
      s_pre_pad: f[1, n_src_pad] input vector (already × 1/w).
      tiles: f[num_blocks, ts, td] packed dense tiles.
      src_tile / dst_tile / block_first: i32[num_blocks] block tables.

    Returns:
      f[1, num_dst_tiles * td]; caller slices [:, :n].
    """
    num_blocks = tiles.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((1, ts), lambda b, st, dt, bf: (0, st[b])),
            pl.BlockSpec((1, ts, td), lambda b, st, dt, bf: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, td), lambda b, st, dt, bf: (0, dt[b])),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, num_dst_tiles * td),
                                       s_pre_pad.dtype),
        interpret=interpret,
    )(src_tile, dst_tile, block_first, s_pre_pad, tiles)
