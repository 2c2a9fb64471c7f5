"""Multi-device tests (spawned subprocesses — the 512-device forcing must
never leak into the main pytest process, which sees 1 device)."""
import os
import subprocess
import sys

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC
    # every script builds its meshes through the project's Auto-axis helper
    script = "from repro.launch.mesh import make_mesh\n" + script
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_distributed_psi_matches_serial():
    print(_run("""
import numpy as np, jax
from repro.graphs import erdos_renyi
from repro.core import heterogeneous, build_operators, power_psi
from repro.core.distributed import DistributedPsi
g = erdos_renyi(600, 4500, seed=4)
act = heterogeneous(g.n, seed=9)
ref = power_psi(build_operators(g, act), tol=1e-10)
for shape, axes in [((2, 4), ("data", "model")),
                    ((2, 2, 2), ("pod", "data", "model"))]:
    mesh = make_mesh(shape, axes)
    dp = DistributedPsi.from_graph(g, act, mesh)
    psi, iters, gap = dp.run_to_convergence(tol=1e-7, chunk_iters=8)
    err = np.abs(psi - np.asarray(ref.psi)).max()
    assert err < 1e-6, (shape, err)
print("ok")
"""))


def test_driver_restart_and_straggler_flags():
    print(_run("""
import numpy as np, jax, tempfile
from repro.graphs import erdos_renyi
from repro.core import heterogeneous, build_operators, power_psi
from repro.core.distributed import DistributedPsi
from repro.runtime import PsiDriver
g = erdos_renyi(500, 3500, seed=5)
act = heterogeneous(g.n, seed=6)
ref = power_psi(build_operators(g, act), tol=1e-10)
mesh = make_mesh((2, 4), ("data", "model"))
dist = DistributedPsi.from_graph(g, act, mesh)
with tempfile.TemporaryDirectory() as d:
    drv = PsiDriver(dist, ckpt_dir=d, chunk_iters=8)
    rep = drv.run(tol=1e-7, fail_hook=lambda c: c in (1, 3))
    assert rep.restarts == 2
    assert np.abs(rep.psi - np.asarray(ref.psi)).max() < 1e-6
print("ok")
"""))


def test_elastic_remesh_preserves_fixed_point():
    print(_run("""
import numpy as np, jax
from repro.graphs import erdos_renyi
from repro.core import heterogeneous, build_operators, power_psi
from repro.core.distributed import DistributedPsi
from repro.runtime import PsiDriver
g = erdos_renyi(640, 5000, seed=7)
act = heterogeneous(g.n, seed=8)
ref = power_psi(build_operators(g, act), tol=1e-10)
mesh1 = make_mesh((2, 4), ("data", "model"))
dist1 = DistributedPsi.from_graph(g, act, mesh1)
run1 = dist1.make_run(chunk_iters=8)
s1, _ = run1(dist1.arrays.c_src, dist1.arrays)
drv2 = PsiDriver(dist1, chunk_iters=8).remesh(
    make_mesh((4, 2), ("data", "model")), g, act, s1)
dist2 = drv2.dist
run2 = dist2.make_run(chunk_iters=8)
s, gap = drv2._warm_s, np.inf
it = 8
while gap > 1e-7 and it < 400:
    s, gdev = run2(s, dist2.arrays); gap = float(gdev); it += 8
epi = jax.jit(dist2.make_epilogue())
psi = dist2.part.from_src_layout(
    np.asarray(epi(s, dist2.arrays)).reshape(dist2.part.d, -1))
assert np.abs(psi - np.asarray(ref.psi)).max() < 1e-6
print("ok, resumed at iter", it)
"""))


def test_remeshed_driver_consumes_warm_start():
    """Regression: remesh() hands the driver a warm vector and run() must
    actually consume it — the re-meshed run converges in fewer iterations
    than a cold driver on the same mesh."""
    print(_run("""
import numpy as np, jax
from repro.graphs import erdos_renyi
from repro.core import heterogeneous
from repro.core.distributed import DistributedPsi
from repro.runtime import PsiDriver
g = erdos_renyi(640, 5000, seed=7)
act = heterogeneous(g.n, seed=8)
mesh1 = make_mesh((2, 4), ("data", "model"))
dist1 = DistributedPsi.from_graph(g, act, mesh1)
# progress the contraction a few chunks on the old mesh
run1 = dist1.make_run(chunk_iters=8)
s1 = dist1.arrays.c_src
for _ in range(3):
    s1, _ = run1(s1, dist1.arrays)
mesh2 = make_mesh((4, 2), ("data", "model"))
warm_drv = PsiDriver(dist1, chunk_iters=8).remesh(mesh2, g, act, s1)
warm = warm_drv.run(tol=1e-7)
cold = PsiDriver(warm_drv.dist, chunk_iters=8).run(tol=1e-7)
assert warm.iterations < cold.iterations, (warm.iterations, cold.iterations)
assert np.abs(warm.psi - cold.psi).max() < 1e-6
print("ok: warm", warm.iterations, "< cold", cold.iterations)
"""))


def test_dispatch_finalize_halves_match_fused_step():
    """The PartialReduction split (compute half / collective half) is
    iteration-equivalent to the fused step on a real 2×4 mesh, and driving
    the whole contraction through the halves reaches the serial ψ."""
    print(_run("""
import numpy as np, jax
from repro.graphs import erdos_renyi
from repro.core import heterogeneous, build_operators, power_psi
from repro.core.distributed import DistributedPsi
g = erdos_renyi(600, 4500, seed=4)
act = heterogeneous(g.n, seed=9)
ref = power_psi(build_operators(g, act), tol=1e-10)
mesh = make_mesh((2, 4), ("data", "model"))
dp = DistributedPsi.from_graph(g, act, mesh)
step = jax.jit(dp.make_step())
dispatch = jax.jit(dp.make_dispatch())
finalize = jax.jit(dp.make_finalize())
s = dp.arrays.c_src
gap = np.inf
for it in range(200):
    s_fused, gap_fused = step(s, dp.arrays)
    s, gap = finalize(dispatch(s, dp.arrays), dp.arrays)
    assert np.allclose(np.asarray(s), np.asarray(s_fused), rtol=1e-6), it
    assert abs(float(gap) - float(gap_fused)) <= 1e-6 * max(float(gap), 1e-30)
    if float(gap) <= 1e-7:
        break
epi = jax.jit(dp.make_epilogue())
psi = dp.part.from_src_layout(
    np.asarray(epi(s, dp.arrays)).reshape(dp.part.d, -1))
assert np.abs(psi - np.asarray(ref.psi)).max() < 1e-6
print("ok at iter", it)
"""))


def test_sharded_embedding_lookup_and_grads():
    print(_run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.recsys.embedding import sharded_lookup
mesh = make_mesh((2, 4), ("data", "model"))
tbl = jnp.asarray(np.random.default_rng(0).normal(size=(64, 8))
                  .astype(np.float32))
tbl_s = jax.device_put(tbl, NamedSharding(mesh, P("model", None)))
ids = jnp.asarray(np.random.default_rng(1).integers(0, 64, (8, 3)))
out = sharded_lookup(tbl_s, ids, mesh, batch_axes=("data",))
assert float(jnp.abs(out - jnp.take(tbl, ids, axis=0)).max()) == 0.0
g = jax.grad(lambda t: jnp.sum(
    sharded_lookup(t, ids, mesh, batch_axes=("data",)) ** 2))(tbl_s)
gr = jax.grad(lambda t: jnp.sum(jnp.take(t, ids, axis=0) ** 2))(tbl)
assert float(jnp.abs(g - gr).max()) == 0.0
print("ok")
"""))


def test_lm_sharded_step_runs():
    """Reduced tinyllama train step on a real 2×4 mesh with its full
    sharding pipeline (FSDP+TP constraints, MoE shard_map)."""
    print(_run("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.models.transformer import init_params, make_train_step, param_specs
from repro.train import adamw, constant_schedule
import dataclasses
mesh = make_mesh((2, 4), ("data", "model"))
for arch in ("tinyllama-1.1b", "mixtral-8x7b"):
    cfg = get_arch(arch).config(reduced=True)
    # reduced dims divisible by the 4-way model axis already (multiples of 8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    from jax.sharding import NamedSharding
    specs = param_specs(cfg, mesh)
    params = jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: hasattr(x, "shape"))
    opt = adamw(constant_schedule(1e-3))
    state = opt.init(params)
    step = jax.jit(make_train_step(cfg, mesh, opt))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (8, 17)))
    batch = dict(tokens=toks[:, :-1], labels=toks[:, 1:])
    for _ in range(2):
        params, state, loss = step(params, state, batch)
    assert np.isfinite(float(loss)), arch
print("ok")
"""))


def test_1d_baseline_matches_serial():
    """Paper-faithful 1-D distribution (replicated s, full psum) — the
    §Perf comparison baseline for the 2-D block-cyclic schedule."""
    print(_run("""
import numpy as np, jax, jax.numpy as jnp
from repro.graphs import erdos_renyi
from repro.core import heterogeneous, build_operators, power_psi
from repro.core.distributed import DistributedPsi1D
g = erdos_renyi(500, 3600, seed=12)
act = heterogeneous(g.n, seed=13)
mesh = make_mesh((8,), ("all",))
d1 = DistributedPsi1D(g, act, mesh)
step = jax.jit(d1.make_step())
a = d1.arrays
s = a["c"]
for _ in range(80):
    s = step(s, a["src"], a["dst"], a["inv_w"], a["mu"], a["c"])
    jax.block_until_ready(s)   # serialize (CPU communicator quirk)
ops = build_operators(g, act)
ref = power_psi(ops, tol=1e-10)
psi = np.asarray(ops.psi_epilogue(jnp.asarray(np.asarray(s)[:g.n])))
assert np.abs(psi - np.asarray(ref.psi)).max() < 1e-6
print("ok")
"""))


def test_sharded_2d_sage_matches_serial():
    """§Perf cell-3 optimization: 2-D block-cyclic message passing."""
    print(_run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.graphs import erdos_renyi
from repro.models.gnn import sage
from repro.models.gnn.common import batch_from_graph
from repro.models.gnn.sharded_mp import build_sharded_graph, sharded_sage_apply
g = erdos_renyi(600, 4200, seed=2)
cfg = sage.SageConfig(d_feat=16, n_classes=5, d_hidden=32, n_layers=2)
rng = np.random.default_rng(0)
x = rng.normal(size=(g.n, 16)).astype(np.float32)
params = sage.init_params(cfg, jax.random.PRNGKey(0))
ref = np.asarray(sage.apply(
    params, batch_from_graph(g, x, labels=rng.integers(0, 5, g.n)), cfg))
mesh = make_mesh((2, 4), ("data", "model"))
part, sg = build_sharded_graph(g, mesh, bidirectional=True)
x_shard = jax.device_put(
    np.stack([part.to_src_layout(x[:, j]) for j in range(16)], -1),
    NamedSharding(mesh, P(("data",), None, None)))
out = sharded_sage_apply(params, x_shard, part, sg, mesh, cfg)
out_nodes = np.stack([part.from_src_layout(np.asarray(out)[..., j])
                      for j in range(out.shape[-1])], -1)
assert np.abs(out_nodes - ref).max() < 1e-5
print("ok")
"""))
