"""Compile the ψ path's kernels for a described TPU v5e, without a chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
gathers it cannot lower, scalar stores to VMEM, blocks that overflow VMEM or
SMEM. These tests lower the kernels of the main path at the width of the
paper's Twitter graph and at the benchmark cells' shapes for one chip of a
described ``v5e:2x2`` topology and compile them with the TPU compiler.
Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import make_edge_tile_step, make_reference_step
from repro.core.operators import PsiOperators
from repro.kernels.autotune import BSR_CANDIDATES, EDGE_TILE_CANDIDATES
from repro.kernels.bsr_spmv import bsr_spmv_call
from repro.kernels.edge_spmv import edge_spmv_call
from repro.kernels.gather import fits_vmem, gather_call
from repro.kernels.ops import DeviceEdgeTiles, power_step
from repro.kernels.power_step import call_starts, power_step_call

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
# the benchmark cells' plans: Graph500 scale 21 at tile 512, 33,706 blocks,
# and scale 23, 136,268 blocks, whose scalar-prefetch tables (1.57 MiB)
# outgrow SMEM in one call and whose 33.5 MB gather source stays in VMEM
CELL_SHAPE = pytest.param(512, 8, 128, 1 << 21, 33_706, id="g500-s21-512")
S23_SHAPE = pytest.param(512, 8, 128, 1 << 23, 136_268, id="g500-s23-512")
# the split step: scale 23's cell, and a block count of scale 24 (~300k)
SPLIT_SHAPES = [S23_SHAPE,
                pytest.param(512, 8, 128, 1 << 24, 300_000, id="scale24-512")]


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled, name):
    """The kernel is compiled for the chip, with its name in its op_name:
    a profiler trace finds it by that name."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"/{name}/" in text


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
    _assert_kernel(compiled, "psi_power_step")


@pytest.mark.parametrize("tile,e1,e2,n,nb", SPLIT_SHAPES)
def test_split_power_step_compiles(sd, tile, e1, e2, n, nb):
    """The whole step at a block count past one call's SMEM: the gather
    kernel, then one ``psi_power_step`` call per tile-aligned block range,
    each under the ``psi.power_step`` scope."""
    num_tiles = -(-n // tile)
    per_tile = np.full(num_tiles, nb // num_tiles)
    per_tile[:nb - per_tile.sum()] += 1
    starts = call_starts(np.repeat(np.arange(num_tiles, dtype=np.int32),
                                   per_tile))
    assert len(starts) >= 2
    blocks = sd((nb,), I32)
    fmt = DeviceEdgeTiles(
        n=n, n_pad=num_tiles * tile, n_gather=num_tiles * tile + 128,
        tile=tile, e1=e1, e2=e2, num_tiles=num_tiles,
        src_idx=sd((nb, e1, e2), I32), dst_local=sd((nb, e1, e2), I32),
        block_tile=blocks, block_first=blocks, block_last=blocks,
        call_starts=starts)
    vec = sd((1, fmt.n_pad), F32)
    compiled = _compile(
        lambda s, w, mu, c, f: power_step(s, w, mu, c, f, interpret=False),
        vec, sd((1, fmt.n_gather), F32), vec, vec, fmt)
    _assert_kernel(compiled, "psi_power_step")
    _assert_kernel(compiled, "psi_gather")
    text = compiled.as_text()
    calls = set(re.findall(r"%(psi_power_step(?:\.\d+)?) = ", text))
    assert len(calls) == len(starts) + 1
    assert "psi.power_step/jit(power_step_call)/psi_power_step/" in text


@pytest.mark.parametrize("tile,e1,e2,n,nb",
                         EDGE_TILE_SHAPES + [CELL_SHAPE, S23_SHAPE])
def test_gather_compiles(sd, tile, e1, e2, n, nb):
    """The whole gather source resident in VMEM, the slot indices streamed."""
    n_gather = -(-n // tile) * tile + 128
    assert fits_vmem(n_gather, e2, F32)
    compiled = _compile(gather_call, sd((1, n_gather), F32),
                        sd((nb, e1, e2), I32))
    _assert_kernel(compiled, "psi_gather")


@pytest.mark.parametrize("tile,e1,e2,n,nb", EDGE_TILE_SHAPES)
def test_edge_spmv_compiles(sd, tile, e1, e2, n, nb):
    num_tiles = -(-n // tile)
    blocks = sd((nb,), I32)
    compiled = _compile(
        lambda *a: edge_spmv_call(*a, tile=tile, e1=e1, e2=e2,
                                  num_tiles=num_tiles),
        sd((nb, e1, e2), F32), sd((nb, e1, e2), I32), blocks, blocks)
    _assert_kernel(compiled, "psi_edge_spmv")


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
    _assert_kernel(compiled, "psi_bsr_spmv")


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
    _assert_kernel(compiled, "psi_power_step")
    # the gather kernel, batched over the lanes, keeps its scope through
    # the compile, and no XLA gather is left in the step
    text = compiled.as_text()
    assert "vmap(psi.gather)/jit(gather_call)/psi_gather/" in text
    assert not re.search(r"\sgather\(", text)


def test_reference_step_compiles(sd):
    """The XLA segment-sum step (``reference`` backend) at Twitter width."""
    edges, nodes = sd((M,), I32), sd((N,), F32)
    ops = PsiOperators(n=N, m=M, src_by_dst=edges, dst_by_dst=edges,
                       src_by_src=edges, dst_by_src=edges, lam=nodes,
                       mu=nodes, inv_w=nodes, c=nodes, d=nodes,
                       b_norm=sd((), F32))
    compiled = _compile(make_reference_step("l1"), ops, nodes)
    assert compiled.as_text()  # XLA only: no kernel to look for
