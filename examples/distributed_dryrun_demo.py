"""Distributed ψ on a simulated 8-device mesh: exactness, restart, remesh.

    PYTHONPATH=src python examples/distributed_dryrun_demo.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import tempfile

import numpy as np
import jax

from repro.graphs import powerlaw_configuration
from repro.core import heterogeneous, build_operators, power_psi
from repro.core.distributed import DistributedPsi
from repro.launch.mesh import make_mesh
from repro.runtime import PsiDriver


def main():
    g = powerlaw_configuration(20_000, 140_000, seed=3, name="demo")
    act = heterogeneous(g.n, seed=4)
    ref = power_psi(build_operators(g, act), tol=1e-9)

    mesh = make_mesh((2, 4), ("data", "model"))
    dist = DistributedPsi.from_graph(g, act, mesh)
    print(f"partition imbalance (straggler indicator): "
          f"{dist.part.imbalance:.3f}")

    with tempfile.TemporaryDirectory() as d:
        drv = PsiDriver(dist, ckpt_dir=d, chunk_iters=16)
        rep = drv.run(tol=1e-7, fail_hook=lambda c: c == 2)
        err = np.abs(rep.psi - np.asarray(ref.psi)).max()
        print(f"2×4 mesh: {rep.iterations} iters, {rep.restarts} restart(s) "
              f"injected+recovered, err vs serial {err:.2e}")

    # elastic: continue the same job on a 4×2 mesh
    run = dist.make_run(chunk_iters=16)
    s_mid, _ = run(dist.arrays.c_src, dist.arrays)
    drv2 = PsiDriver(dist, chunk_iters=16).remesh(
        make_mesh((4, 2), ("data", "model")), g, act, s_mid)
    d2 = drv2.dist
    run2 = d2.make_run(chunk_iters=16)
    s, gap, it = drv2._warm_s, np.inf, 16
    while gap > 1e-7 and it < 400:
        s, gd = run2(s, d2.arrays)
        gap = float(gd)
        it += 16
    epi = jax.jit(d2.make_epilogue())
    psi = d2.part.from_src_layout(
        np.asarray(epi(s, d2.arrays)).reshape(d2.part.d, -1))
    print(f"elastic 2×4→4×2 re-mesh: resumed warm, err "
          f"{np.abs(psi - np.asarray(ref.psi)).max():.2e}")


if __name__ == "__main__":
    main()
