"""End-to-end driver: train a small LM with ψ-weighted data curation.

The paper's technique as a first-class data-layer feature (DESIGN.md §5):
documents belong to synthetic users of a social graph; training batches
sample authors ∝ ψ-score, i.e. influence-curated mixing. Trains a reduced
TinyLlama-family model with the full production substrate — sharded step,
checkpointing, resume.

    PYTHONPATH=src python examples/train_lm_psi_sampling.py \
        --steps 60 --d-model 128 --layers 4
(defaults are CPU-sized; scale flags up on real hardware)
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core import heterogeneous, build_operators, power_psi
from repro.graphs import powerlaw_configuration
from repro.launch.mesh import make_mesh
from repro.data import TokenPipeline, PsiWeightedSampler
from repro.models.transformer import LMConfig, init_params, make_train_step
from repro.train import adamw, cosine_schedule
from repro.ckpt import checkpoint


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--ckpt-dir", default="/tmp/psi_lm_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    # 1. ψ-scores over the author graph → sampling weights
    g = powerlaw_configuration(5000, 40_000, seed=11, name="authors")
    ops = build_operators(g, heterogeneous(g.n, seed=12))
    psi = np.asarray(power_psi(ops, tol=1e-8).psi)
    sampler = PsiWeightedSampler(psi, temperature=1.0, seed=13)
    print("ψ-curation:", sampler.mixture_stats())

    # 2. model + substrate
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = LMConfig(name="psi-lm", n_layers=args.layers,
                   d_model=args.d_model, n_heads=max(2, args.d_model // 32),
                   n_kv_heads=max(1, args.d_model // 64), vocab=args.vocab,
                   d_ff=args.d_model * 3, dtype=jnp.float32,
                   param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw(cosine_schedule(3e-3, args.steps, max(1, args.steps // 10)))
    state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, mesh, opt))
    pipe = TokenPipeline(vocab=args.vocab, seq_len=args.seq,
                         global_batch=args.batch, seed=5)

    start = 0
    if args.resume and checkpoint.latest_step(args.ckpt_dir) is not None:
        start = checkpoint.latest_step(args.ckpt_dir)
        data = checkpoint.restore(args.ckpt_dir, start,
                                  dict(params=params, opt=state))
        params, state = data["params"], data["opt"]
        print(f"resumed from step {start}")

    # 3. train loop: author ids drawn ∝ ψ seed the per-step data stream
    t0 = time.perf_counter()
    for step in range(start, args.steps):
        authors = sampler.sample_users(args.batch)
        raw = pipe.batch(step)
        # author id modulates the stream (stand-in for per-author corpora)
        tok = (raw["tokens"] + authors[:, None]) % args.vocab
        lab = (raw["labels"] + authors[:, None]) % args.vocab
        batch = dict(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))
        params, state, loss = step_fn(params, state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(loss):.4f} "
                  f"({(time.perf_counter() - t0):.1f}s)")
        if (step + 1) % 20 == 0:
            checkpoint.save(args.ckpt_dir, step + 1,
                            dict(params=params, opt=state))
    print("done; final checkpoint:",
          checkpoint.save(args.ckpt_dir, args.steps,
                          dict(params=params, opt=state)))


if __name__ == "__main__":
    main()
