"""The ``psi_gather`` kernel against the XLA gather it replaces (interpret
mode): the same slot values bit for bit, on the planner's edge-tile shapes,
batched the way the fleet runs it, and the XLA path kept above the kernel's
VMEM budget."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.graphs import erdos_renyi, powerlaw_configuration
from repro.kernels import build_edge_tiles, DeviceEdgeTiles, power_step
from repro.kernels import gather
from repro.kernels.autotune import EDGE_TILE_CANDIDATES
from repro.kernels.gather import fits_vmem, gather_call
from repro.kernels.ops import gather_step_edges
from repro.obs import metrics as obs_metrics

GRAPHS = [
    # n = 700, 40, 1000 are not multiples of 128; 1000 nodes at tile 128
    # span eight node tiles
    ("powerlaw", lambda: powerlaw_configuration(700, 4200, seed=3)),
    ("tiny", lambda: erdos_renyi(40, 80, seed=4)),
    ("er-tiles", lambda: erdos_renyi(1000, 3000, seed=5)),
]


def _source(fmt, seed=0):
    """f[1, n_gather]: random values at the n users, zeros beyond."""
    x = np.zeros((1, fmt.n_gather), np.float32)
    x[0, :fmt.n] = np.random.default_rng(seed).uniform(0.01, 1.0, fmt.n)
    return jnp.asarray(x)


def _fmt(g, tile, e1, e2):
    return DeviceEdgeTiles.from_format(build_edge_tiles(g, tile=tile, e1=e1,
                                                        e2=e2))


@pytest.fixture
def registry():
    prev = obs_metrics.set_registry(obs_metrics.MetricsRegistry())
    yield obs_metrics.get_registry()
    obs_metrics.set_registry(prev)


def _paths(registry) -> dict:
    fam = registry.to_json().get("psi_gather_path_total", {"series": []})
    return {s["labels"]["path"]: s["value"] for s in fam["series"]}


@pytest.mark.parametrize("gname,gfn", GRAPHS)
@pytest.mark.parametrize("tile,e1,e2",
                         EDGE_TILE_CANDIDATES + ((512, 16, 128),))
def test_psi_gather_equals_the_xla_gather(gname, gfn, tile, e1, e2):
    g = gfn()
    fmt = _fmt(g, tile, e1, e2)
    x = _source(fmt)
    src = np.asarray(fmt.src_idx)
    assert (src == g.n).any()                     # sentinel slots are there
    assert fits_vmem(fmt.n_gather, e2, x.dtype)
    out = gather_call(x, fmt.src_idx, interpret=True)
    assert out.shape == fmt.src_idx.shape and out.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(fmt.gather_edges(x)))
    assert not np.asarray(out)[src == g.n].any()  # sentinels read zero


def test_psi_gather_batches_under_vmap():
    """The fleet's lanes: each lane its own source and indices."""
    rng = np.random.default_rng(2)
    lanes, n = 3, 700
    n_gather = -(-n // 128) * 128 + 128
    x = np.zeros((lanes, 1, n_gather), np.float32)
    x[:, 0, :n] = rng.uniform(size=(lanes, n))
    idx = rng.integers(0, n + 1, (lanes, 4, 8, 128)).astype(np.int32)
    out = jax.vmap(lambda a, b: gather_call(a, b, interpret=True))(
        jnp.asarray(x), jnp.asarray(idx))
    want = np.stack([x[k, 0][idx[k]] for k in range(lanes)])
    np.testing.assert_array_equal(np.asarray(out), want)


def test_power_step_gathers_in_vmem_and_counts_the_path(registry):
    g = powerlaw_configuration(700, 4200, seed=3)
    fmt = _fmt(g, 256, 8, 128)
    x = _source(fmt)
    vals = gather_step_edges(x, fmt, interpret=True)
    np.testing.assert_array_equal(np.asarray(vals),
                                  np.asarray(fmt.gather_edges(x)))
    assert _paths(registry) == {"vmem": 1.0}


def test_over_the_vmem_budget_the_xla_gather_stays(registry, monkeypatch):
    """A source over the budget takes the XLA path, and the fused step
    gives the same s' and gap bit for bit on either path."""
    g = powerlaw_configuration(700, 4200, seed=3)
    fmt = _fmt(g, 256, 8, 128)
    rng = np.random.default_rng(3)
    s = jnp.asarray(rng.uniform(size=(1, fmt.n_pad)).astype(np.float32))
    inv_w = _source(fmt, seed=4)
    mu, c = (jnp.asarray(rng.uniform(size=(1, fmt.n_pad)).astype(np.float32))
             for _ in range(2))
    vmem = power_step(s, inv_w, mu, c, fmt, interpret=True)
    monkeypatch.setattr(gather, "VMEM_SOURCE_BUDGET", fmt.n_gather * 4 - 4)
    assert not fits_vmem(fmt.n_gather, fmt.e2, jnp.float32)
    xla = power_step(s, inv_w, mu, c, fmt, interpret=True)
    assert _paths(registry) == {"vmem": 1.0, "xla": 1.0}
    for a, b in zip(vmem, xla):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n_gather,e2,dtype,fits", [
    (2_097_280, 128, jnp.float32, True),            # Graph500 scale 21
    (8_388_736, 128, jnp.float32, True),            # scale 23, 33.5 MB
    (16_777_344, 128, jnp.float32, True),           # scale 24, 67.1 MB
    (gather.VMEM_SOURCE_BUDGET // 4, 128, jnp.float32, True),
    (gather.VMEM_SOURCE_BUDGET // 4 + 128, 128, jnp.float32, False),
    (2_097_280, 64, jnp.float32, False),            # not a lane-wide row
    (2_097_280, 128, jnp.bfloat16, False),
])
def test_fits_vmem_is_a_shape_test(n_gather, e2, dtype, fits):
    assert fits_vmem(n_gather, e2, dtype) is fits


def test_vmem_budget_is_the_chips_vmem_less_the_slack():
    """A call asks for the source and the slack: at the budget, all of
    v5e's 128 MiB of VMEM a core."""
    assert gather.VMEM_SOURCE_BUDGET + gather._VMEM_SLACK == 128 * 2**20
