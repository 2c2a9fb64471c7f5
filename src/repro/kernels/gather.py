"""Per-edge gather ``x[src_idx]`` from a VMEM-resident source vector.

The edge-tile step needs each edge slot's source value ``(s ⊙ 1/w)[src]``
before its one-hot scatter. An XLA gather reads each slot at random from
HBM, one 4-byte load per slot, and is bound by per-element latency. The
source vector is small (8.4 MB at 2.1M users, 33.5 MB at 8.4M), so this
kernel holds all of it in VMEM for the whole call, laid out as
``[n_gather // 128, 128]``, and gathers there, one edge block per grid step
and one row of 128 slots at a time:

  1. for each slot ``j`` of the row, read its index from SMEM and load the
     source row ``idx >> 7`` (one single-sublane vector load) into row ``j``
     of a ``[128, 128]`` scratch;
  2. transpose the scratch, so that slot ``j``'s source row becomes
     column ``j``;
  3. pick lane ``idx & 127`` of each column by a compare against a sublane
     iota and a sublane sum (the other 127 terms are exact zeros).

The indices come in twice: as an SMEM block for the scalar row reads and as
a VMEM block for the vector lane select. No slot touches HBM at random; the
kernel streams the indices and the output once. Sentinel slots (index
``n``) read the zero the caller pads there. The result equals
``x[0][src_idx]`` bit for bit.

The kernel engages only where the source fits :data:`VMEM_SOURCE_BUDGET`
(:func:`fits_vmem`); above it, callers keep the XLA gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["VMEM_SOURCE_BUDGET", "fits_vmem", "gather_call"]

LANES = 128
_V5E_VMEM = 128 * 1024 * 1024          # per core
# VMEM a call asks for beyond the source: the index and output blocks,
# double-buffered (16 KiB at e1 = 8), the [128, 128] scratch (64 KiB) and
# Mosaic's own
_VMEM_SLACK = 8 * 1024 * 1024
# bytes of gather source a kernel call may keep resident in VMEM: all of
# v5e's VMEM less the slack, 120 MiB, the source of ~31.4M users in float32
VMEM_SOURCE_BUDGET = _V5E_VMEM - _VMEM_SLACK


def fits_vmem(n_gather: int, e2: int, dtype) -> bool:
    """Whether :func:`gather_call` takes this shape: a lane-wide block row
    and a float32 source of at most :data:`VMEM_SOURCE_BUDGET` bytes."""
    return (e2 == LANES and n_gather % LANES == 0
            and jnp.dtype(dtype) == jnp.float32
            and n_gather * jnp.dtype(dtype).itemsize <= VMEM_SOURCE_BUDGET)


def _make_kernel(e1: int, unroll: bool):
    def kernel(idx_smem, idx_vmem, x_ref, out_ref, rows_ref):
        sublane = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        # the block's rows stay unrolled: the scheduler then runs each
        # row's transpose and pick beside the next row's loads
        for r in range(e1):
            def load_slot(j, carry):
                i = idx_smem[0, r, j]
                rows_ref[pl.ds(j, 1), :] = x_ref[pl.ds(i >> 7, 1), :]
                return carry

            jax.lax.fori_loop(0, LANES, load_slot, 0, unroll=unroll)
            lane = idx_vmem[0, pl.ds(r, 1), :] & 127          # [1, 128]
            cols = rows_ref[...].T                            # [lane, slot]
            out_ref[0, pl.ds(r, 1), :] = jnp.sum(
                jnp.where(sublane == lane, cols, 0), axis=0, keepdims=True)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_call(x: jax.Array, src_idx: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """``x[0][src_idx]`` with ``x`` resident in VMEM, one edge block per
    grid step.

    Args:
      x: f[1, n_gather], ``n_gather`` a multiple of 128, within
        :data:`VMEM_SOURCE_BUDGET` (:func:`fits_vmem`).
      src_idx: i32[num_blocks, e1, 128], every index in ``[0, n_gather)``.

    Returns:
      f[num_blocks, e1, 128].
    """
    num_blocks, e1, e2 = src_idx.shape
    n_gather = x.shape[-1]
    block = pl.BlockSpec((1, e1, e2), lambda b: (b, 0, 0))
    return pl.pallas_call(
        # Mosaic unrolls the 128-slot loop fully or not at all, and rolled
        # it runs ~7x slower on a v5e; interpret mode keeps it rolled
        _make_kernel(e1, unroll=not interpret),
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((1, e1, e2), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            block,
            pl.BlockSpec((n_gather // LANES, LANES), lambda b: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
        ],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(src_idx.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((LANES, LANES), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=n_gather * x.dtype.itemsize + _VMEM_SLACK),
        interpret=interpret,
        name="psi_gather",
    )(src_idx, src_idx, x.reshape(n_gather // LANES, LANES))
