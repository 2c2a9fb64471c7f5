"""Segment-sum of feature rows as one-hot MXU matmuls (GNN aggregation).

The message-passing primitive Y[i] = Σ_{e: dst_e = i} M[e, :] shared by
GraphSAGE / PNA / NequIP / EquiformerV2 aggregation and by the EmbeddingBag
reduce in the recsys stack. Per edge block:

    out[dst_tile]  +=  one_hotᵀ @ M_block       # [tile, eblk] @ [eblk, d] MXU

with edges pre-sorted/padded by ``formats.build_edge_tiles`` bookkeeping so
each block maps to one output node tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["seg_mm_call"]


def _kernel(block_tile_ref, first_ref, msg_ref, dstl_ref, out_ref, *,
            tile: int):
    b = pl.program_id(0)

    @pl.when(first_ref[b] == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    msg = msg_ref[0]                                   # [eblk, d]
    dstl = dstl_ref[0]                                 # [1, eblk] i32
    eblk = msg.shape[0]
    onehot_t = (jax.lax.broadcasted_iota(jnp.int32, (tile, eblk), 0) ==
                dstl).astype(msg.dtype)                # [tile, eblk]
    out_ref[0] += jnp.dot(onehot_t, msg,
                          preferred_element_type=msg.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "eblk", "num_tiles",
                                             "interpret"))
def seg_mm_call(messages: jax.Array, dst_local: jax.Array,
                block_tile: jax.Array, block_first: jax.Array, *,
                tile: int, eblk: int, num_tiles: int,
                interpret: bool = False) -> jax.Array:
    """Raw pallas_call: blocked segment-sum of message rows.

    Args:
      messages: f[num_blocks, eblk, d] — edge features, dst-sorted/padded
        (padding rows are zero).
      dst_local: i32[num_blocks, 1, eblk] — dst − tile_base per edge.
      block_tile / block_first: i32[num_blocks].

    Returns:
      f[num_tiles * tile, d].
    """
    num_blocks, _, d = messages.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((1, eblk, d), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, eblk), lambda b, *_: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile, d), lambda b, bt, bf: (bt[b], 0, 0)),
    )
    out = pl.pallas_call(
        _kernel_wrapper(tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tiles, tile, d), messages.dtype),
        interpret=interpret,
        name="psi_seg_mm",
    )(block_tile, block_first, messages, dst_local)
    return out.reshape(num_tiles * tile, d)


def _kernel_wrapper(tile: int):
    return functools.partial(_kernel, tile=tile)
