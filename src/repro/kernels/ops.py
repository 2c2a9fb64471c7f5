"""Public jit'd wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU: on a TPU the kernels compile through
Mosaic; on the CPU (the test suite runs with ``JAX_PLATFORMS=cpu``) the same
kernels run in Pallas interpret mode. Entry points that must not fall back
to the CPU check the platform themselves (``chip_smoke.py``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .formats import BsrFormat, EdgeTileFormat, build_bsr, build_edge_tiles
from .edge_spmv import edge_spmv_call
from .bsr_spmv import bsr_spmv_call
from .power_step import power_step_call
from .seg_mm import seg_mm_call

__all__ = [
    "default_interpret", "DeviceEdgeTiles", "DeviceBsr",
    "edge_spmv", "bsr_spmv", "seg_mm", "power_step", "PsiKernelEngine",
]


def default_interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


# --------------------------------------------------------------------- #
# Device-resident format mirrors (pytrees: arrays data, sizes static)
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class DeviceEdgeTiles:
    n: int
    n_pad: int            # num_tiles * tile
    n_gather: int         # padded gather-source length (sentinel slots zero)
    tile: int
    e1: int
    e2: int
    num_tiles: int
    src_idx: jax.Array
    dst_local: jax.Array
    block_tile: jax.Array
    block_first: jax.Array
    block_last: jax.Array

    @classmethod
    def from_format(cls, fmt: EdgeTileFormat) -> "DeviceEdgeTiles":
        return cls(
            n=fmt.n, n_pad=fmt.num_tiles * fmt.tile,
            n_gather=fmt.num_tiles * fmt.tile + 128,
            tile=fmt.tile, e1=fmt.e1, e2=fmt.e2, num_tiles=fmt.num_tiles,
            src_idx=jnp.asarray(fmt.src_idx),
            dst_local=jnp.asarray(fmt.dst_local),
            block_tile=jnp.asarray(fmt.block_tile),
            block_first=jnp.asarray(fmt.block_first),
            block_last=jnp.asarray(fmt.block_last))

    def pad_gather_source(self, s_pre: jax.Array) -> jax.Array:
        """f[n] → f[1, n_gather] with zeros beyond n (sentinel = n)."""
        return jnp.pad(s_pre, (0, self.n_gather - s_pre.shape[0]))[None, :]

    def gather_edges(self, s_pre_pad: jax.Array) -> jax.Array:
        """f[1, n_gather] → f[num_blocks, e1, e2]: each slot's source value
        (sentinel slots read the zero at index n)."""
        return s_pre_pad[0][self.src_idx]

    def pad_node_vector(self, v: jax.Array) -> jax.Array:
        return jnp.pad(v, (0, self.n_pad - v.shape[0]))[None, :]


jax.tree_util.register_dataclass(
    DeviceEdgeTiles,
    data_fields=["src_idx", "dst_local", "block_tile", "block_first",
                 "block_last"],
    meta_fields=["n", "n_pad", "n_gather", "tile", "e1", "e2", "num_tiles"])


@dataclasses.dataclass(frozen=True)
class DeviceBsr:
    n: int
    n_src_pad: int
    ts: int
    td: int
    num_dst_tiles: int
    tiles: jax.Array
    src_tile: jax.Array
    dst_tile: jax.Array
    block_first: jax.Array

    @classmethod
    def from_format(cls, fmt: BsrFormat) -> "DeviceBsr":
        return cls(n=fmt.n, n_src_pad=fmt.n_src_pad, ts=fmt.ts, td=fmt.td,
                   num_dst_tiles=fmt.num_dst_tiles,
                   tiles=jnp.asarray(fmt.tiles),
                   src_tile=jnp.asarray(fmt.src_tile),
                   dst_tile=jnp.asarray(fmt.dst_tile),
                   block_first=jnp.asarray(fmt.block_first))


jax.tree_util.register_dataclass(
    DeviceBsr, data_fields=["tiles", "src_tile", "dst_tile", "block_first"],
    meta_fields=["n", "n_src_pad", "ts", "td", "num_dst_tiles"])


# --------------------------------------------------------------------- #
# Functional wrappers
# --------------------------------------------------------------------- #
def edge_spmv(s_pre: jax.Array, fmt: DeviceEdgeTiles,
              weights: jax.Array | None = None,
              interpret: bool | None = None) -> jax.Array:
    """t_i = Σ_{(j→i)} w_e s_pre_j via the edge-tile kernel. Returns f[n]."""
    interpret = default_interpret() if interpret is None else interpret
    vals = fmt.gather_edges(fmt.pad_gather_source(s_pre))
    if weights is not None:
        vals = vals * weights
    out = edge_spmv_call(
        vals, fmt.dst_local, fmt.block_tile, fmt.block_first,
        tile=fmt.tile, e1=fmt.e1, e2=fmt.e2, num_tiles=fmt.num_tiles,
        interpret=interpret)
    return out[0, :fmt.n]


def bsr_spmv(s_pre: jax.Array, fmt: DeviceBsr,
             interpret: bool | None = None) -> jax.Array:
    """t = s_preᵀ A via dense MXU tiles. Returns f[n]."""
    interpret = default_interpret() if interpret is None else interpret
    s_pad = jnp.pad(s_pre, (0, fmt.n_src_pad - s_pre.shape[0]))[None, :]
    out = bsr_spmv_call(s_pad, fmt.tiles, fmt.src_tile, fmt.dst_tile,
                        fmt.block_first, ts=fmt.ts, td=fmt.td,
                        num_dst_tiles=fmt.num_dst_tiles, interpret=interpret)
    return out[0, :fmt.n]


def seg_mm(messages: jax.Array, fmt: DeviceEdgeTiles,
           interpret: bool | None = None) -> jax.Array:
    """Blocked segment-sum of rows. messages: f[num_blocks, e1*e2, d] in the
    fmt's padded edge order (padding rows zero). Returns f[n, d]."""
    interpret = default_interpret() if interpret is None else interpret
    eblk = fmt.e1 * fmt.e2
    dstl = fmt.dst_local.reshape(-1, 1, eblk)
    out = seg_mm_call(messages, dstl, fmt.block_tile, fmt.block_first,
                      tile=fmt.tile, eblk=eblk, num_tiles=fmt.num_tiles,
                      interpret=interpret)
    return out[:fmt.n]


def power_step(s: jax.Array, inv_w_gather: jax.Array, mu_pad: jax.Array,
               c_pad: jax.Array, fmt: DeviceEdgeTiles,
               interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """One fused Alg. 2 step on padded [1, n_pad] node vectors.

    Args:
      s: f[1, n_pad] current series vector (padded layout).
      inv_w_gather: f[1, n_gather] 1/w in gather layout (zeros in pads).
      mu_pad / c_pad: f[1, n_pad].
    Returns:
      (s_new f[1, n_pad], gap scalar ‖Δs‖₁).
    """
    interpret = default_interpret() if interpret is None else interpret
    s_pre = jnp.pad(s, ((0, 0), (0, fmt.n_gather - fmt.n_pad))) * inv_w_gather
    s_new, gap = power_step_call(
        fmt.gather_edges(s_pre), fmt.dst_local, fmt.block_tile,
        fmt.block_first, fmt.block_last, mu_pad, c_pad, s,
        tile=fmt.tile, e1=fmt.e1, e2=fmt.e2, num_tiles=fmt.num_tiles,
        interpret=interpret)
    return s_new, gap[0, 0]


# --------------------------------------------------------------------- #
# Full Power-ψ on the fused kernel — absorbed by the unified engine
# --------------------------------------------------------------------- #
class PsiKernelEngine:
    """Back-compat shim: the fused-kernel solver now lives in
    ``repro.core.engine`` as the ``pallas`` backend — construct it with
    ``make_engine("pallas", graph=..., activity=...)``. This wrapper keeps
    the historical constructor/run signature working."""

    def __init__(self, graph, activity, *, tile: int = 256, e1: int = 8,
                 e2: int = 128, dtype=jnp.float32,
                 interpret: bool | None = None):
        from ..core.engine import make_engine
        self._engine = make_engine("pallas", graph=graph, activity=activity,
                                   tile=tile, e1=e1, e2=e2, dtype=dtype,
                                   interpret=interpret)
        self.ops = self._engine.ops
        self.fmt = self._engine.fmt
        self.interpret = self._engine.interpret

    def run(self, *, tol: float = 1e-9, max_iter: int = 10_000,
            s0=None):
        return self._engine.run(tol=tol, max_iter=max_iter, s0=s0)
