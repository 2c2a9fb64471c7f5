"""Compile the ψ path's kernels for a described TPU v5e, without a chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
gathers it cannot lower, scalar stores to VMEM, blocks that overflow VMEM or
SMEM. These tests lower the kernels of the main path at the width of the
paper's Twitter graph for one chip of a described ``v5e:2x2`` topology and
compile them with the TPU compiler. Nothing runs, so they say nothing about
results or times.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import make_edge_tile_step, make_reference_step
from repro.core.operators import PsiOperators
from repro.kernels.autotune import BSR_CANDIDATES, EDGE_TILE_CANDIDATES
from repro.kernels.bsr_spmv import bsr_spmv_call
from repro.kernels.edge_spmv import edge_spmv_call
from repro.kernels.ops import DeviceEdgeTiles
from repro.kernels.power_step import power_step_call

# the paper's Twitter graph (Table II), the largest on the ψ serving path
N, M = 465_017, 834_797
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def sd(one_chip):
    """shape, dtype → an argument placed on the described chip."""
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _twitter_blocks(tile, e1, e2):
    """Blocks of an edge-tile format at Twitter width: every tile holds at
    least one block, plus the blocks the edges fill."""
    num_tiles = -(-N // tile)
    return num_tiles + -(-M // (e1 * e2))


# (tile, e1, e2, n, num_blocks): every planner candidate at Twitter width,
# and Graph500 scale 21 with the default plan, whose three scalar-prefetch
# tables of 36,163 blocks must still fit in SMEM
EDGE_TILE_SHAPES = [
    pytest.param(t, a, b, N, _twitter_blocks(t, a, b), id=f"twitter-{t}")
    for t, a, b in EDGE_TILE_CANDIDATES
] + [pytest.param(256, 8, 128, 1 << 21, 36_163, id="rmat21-256")]


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tile,e1,e2,n,nb", EDGE_TILE_SHAPES)
def test_power_step_compiles(sd, tile, e1, e2, n, nb):
    num_tiles = -(-n // tile)
    vec = sd((1, num_tiles * tile), F32)
    blocks = sd((nb,), I32)
    compiled = _compile(
        lambda *a: power_step_call(*a, tile=tile, e1=e1, e2=e2,
                                   num_tiles=num_tiles),
        sd((nb, e1, e2), F32), sd((nb, e1, e2), I32), blocks, blocks, blocks,
        vec, vec, vec)
    _assert_kernel(compiled)


@pytest.mark.parametrize("tile,e1,e2,n,nb", EDGE_TILE_SHAPES)
def test_edge_spmv_compiles(sd, tile, e1, e2, n, nb):
    num_tiles = -(-n // tile)
    blocks = sd((nb,), I32)
    compiled = _compile(
        lambda *a: edge_spmv_call(*a, tile=tile, e1=e1, e2=e2,
                                  num_tiles=num_tiles),
        sd((nb, e1, e2), F32), sd((nb, e1, e2), I32), blocks, blocks)
    _assert_kernel(compiled)


@pytest.mark.parametrize("ts,td", BSR_CANDIDATES)
def test_bsr_spmv_compiles(sd, ts, td):
    n_src_pad = -(-N // ts) * ts
    num_dst_tiles = -(-N // td)
    nb = 2 * num_dst_tiles
    blocks = sd((nb,), I32)
    compiled = _compile(
        lambda *a: bsr_spmv_call(*a, ts=ts, td=td,
                                 num_dst_tiles=num_dst_tiles),
        sd((1, n_src_pad), F32), sd((nb, ts, td), F32), blocks, blocks,
        blocks)
    _assert_kernel(compiled)


def _device_edge_tiles(sd, lanes, tile, e1, e2, n):
    """A DeviceEdgeTiles whose arrays are shapes with ``lanes`` leading."""
    num_tiles = -(-n // tile)
    nb = num_tiles + 4
    blocks = sd((*lanes, nb), I32)
    return DeviceEdgeTiles(
        n=n, n_pad=num_tiles * tile, n_gather=num_tiles * tile + 128,
        tile=tile, e1=e1, e2=e2, num_tiles=num_tiles,
        src_idx=sd((*lanes, nb, e1, e2), I32),
        dst_local=sd((*lanes, nb, e1, e2), I32),
        block_tile=blocks, block_first=blocks, block_last=blocks)


def test_fleet_vmapped_edge_tile_step_compiles(sd):
    """The fleet's pallas regime: one kernel launch for a stack of tenants
    (8 lanes of a 4096-node bucket)."""
    lanes, (tile, e1, e2) = 8, EDGE_TILE_CANDIDATES[0]
    fmt = _device_edge_tiles(sd, (lanes,), tile, e1, e2, 4096)
    vec = sd((lanes, 1, fmt.n_pad), F32)
    args = (fmt, sd((lanes, 1, fmt.n_gather), F32), vec, vec)
    compiled = _compile(jax.vmap(make_edge_tile_step(interpret=False)),
                        args, vec)
    _assert_kernel(compiled)


def test_reference_step_compiles(sd):
    """The XLA segment-sum step (``reference`` backend) at Twitter width."""
    edges, nodes = sd((M,), I32), sd((N,), F32)
    ops = PsiOperators(n=N, m=M, src_by_dst=edges, dst_by_dst=edges,
                       src_by_src=edges, dst_by_src=edges, lam=nodes,
                       mu=nodes, inv_w=nodes, c=nodes, d=nodes,
                       b_norm=sd((), F32))
    compiled = _compile(make_reference_step("l1"), ops, nodes)
    assert compiled.as_text()  # XLA only: no kernel to look for
