"""The edge-tile step split over several ``psi_power_step`` calls, as a
format whose block tables outgrow one call's SMEM runs it (interpret
mode). The cap is lowered in each test, never through an option, so that
a graph of a few thousand users takes three calls or more."""
import importlib

import numpy as np
import pytest

from repro.core import PsiService, exact_psi, heterogeneous, make_engine
from repro.core.operators import build_operators
from repro.graphs import erdos_renyi, powerlaw_configuration
from repro.kernels import DeviceEdgeTiles, build_edge_tiles, power_step
from repro.obs import metrics as obs_metrics

# the module, not the function the package exports under the same name
power_step_mod = importlib.import_module("repro.kernels.power_step")
call_starts = power_step_mod.call_starts


@pytest.fixture
def cap(monkeypatch):
    """``cap(blocks)``: one call's SMEM tables hold that many blocks."""
    def set_cap(blocks):
        monkeypatch.setattr(power_step_mod, "SMEM_TABLE_BUDGET",
                            blocks * power_step_mod._TABLE_BYTES)
    return set_cap


def _graph():
    # 24k follows over 2,000 users: every node tile at the planner's tiles
    # holds a few blocks, and no tile more than eight
    return erdos_renyi(2000, 24000, seed=21)


def _step_args(g, act, fmt):
    ops = build_operators(g, act)
    return (fmt.pad_node_vector(ops.c), fmt.pad_gather_source(ops.inv_w),
            fmt.pad_node_vector(ops.mu), fmt.pad_node_vector(ops.c))


def test_default_cap_is_one_call_up_to_65536_blocks():
    assert power_step_mod.SMEM_TABLE_BUDGET // 12 == 65_536
    assert call_starts(np.repeat(np.arange(4096, dtype=np.int32), 16)) == ()
    # one block more opens a second call, at the last whole tile
    tables = np.repeat(np.arange(4097, dtype=np.int32), 16)
    assert call_starts(tables) == (65_536,)


@pytest.mark.parametrize("blocks", [8, 13, 20])
@pytest.mark.parametrize("tile", [128, 256, 512])
def test_calls_start_at_tiles_and_fit_the_cap(cap, blocks, tile):
    cap(blocks)
    fmt = build_edge_tiles(_graph(), tile=tile)
    starts = call_starts(fmt.block_tile)
    bounds = (0, *starts, fmt.num_blocks)
    assert len(bounds) - 1 >= 2
    assert all(0 < b - a <= blocks for a, b in zip(bounds, bounds[1:]))
    # a node tile's blocks never straddle two calls
    assert all(fmt.block_first[b] == 1 for b in starts)


def test_a_tile_past_the_cap_is_refused(cap):
    cap(2)
    with pytest.raises(ValueError, match="spans more than 2 blocks"):
        call_starts(np.asarray([0, 0, 0, 1], np.int32))


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_split_step_is_bit_identical_to_one_call(cap, tile):
    g = _graph()
    act = heterogeneous(g.n, seed=22)
    host = build_edge_tiles(g, tile=tile)
    one = DeviceEdgeTiles.from_format(host)
    cap(8)
    split = DeviceEdgeTiles.from_format(host)
    assert one.call_starts == () and len(split.call_starts) >= 2
    args = _step_args(g, act, one)
    s_one, gap_one = power_step(*args, one, interpret=True)
    s_split, gap_split = power_step(*args, split, interpret=True)
    # s' and the L1 gap, which the later calls go on summing in place
    np.testing.assert_array_equal(np.asarray(s_split), np.asarray(s_one))
    assert np.asarray(gap_split).tobytes() == np.asarray(gap_one).tobytes()


def test_split_solve_is_bit_identical_to_one_call(cap):
    """The whole solve, through the engine's jitted loop: the same
    iterations, s and ψ."""
    g = powerlaw_configuration(1500, 12000, seed=23)
    act = heterogeneous(g.n, seed=24)
    one = make_engine("pallas", graph=g, activity=act, tile=128)
    blocks = int(np.bincount(one.fmt_host.block_tile).max())
    cap(max(blocks, 4))
    split = make_engine("pallas", graph=g, activity=act, tile=128)
    assert one.fmt.call_starts == () and len(split.fmt.call_starts) >= 2
    r_one, r_split = one.run(tol=1e-8), split.run(tol=1e-8)
    assert int(r_split.iterations) == int(r_one.iterations)
    np.testing.assert_array_equal(np.asarray(r_split.s), np.asarray(r_one.s))
    np.testing.assert_array_equal(np.asarray(r_split.psi),
                                  np.asarray(r_one.psi))


def test_split_service_agrees_with_the_float64_reference(cap):
    g = _graph()
    act = heterogeneous(g.n, seed=25)
    prev = obs_metrics.set_registry(obs_metrics.MetricsRegistry())
    try:
        cap(8)
        svc = PsiService(g, act, tol=1e-9, backend="auto")
        psi = svc.scores()
        calls = obs_metrics.get_registry().value("psi_power_step_calls")
    finally:
        obs_metrics.set_registry(prev)
    assert len(svc.engine.fmt.call_starts) >= 2
    assert calls == len(svc.engine.fmt.call_starts) + 1
    psi_true, _ = exact_psi(g, act)
    # a float32 iterate stopped at tol 1e-9 on ‖B‖·‖Δs‖₁: what is left is
    # float32 rounding, ~1e-7 of each ψ; 1e-5 leaves a hundredfold room
    assert np.max(np.abs(psi - psi_true) / psi_true) <= 1e-5
