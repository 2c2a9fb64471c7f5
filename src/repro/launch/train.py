"""Production training launcher: ``--arch <id>`` → sharded train loop.

On this CPU container it runs reduced configs end-to-end (the full configs
are exercised via dryrun.py); on a real slice the same entrypoint binds the
production mesh, per-host data sharding, checkpoint/restart and the
straggler monitor.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 20
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config (default on this container)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="host", choices=["host"],
                    help="'host': all local devices as (data, model)=(n,1)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from ..configs import get_arch
    from ..ckpt import checkpoint
    from ..data import TokenPipeline
    from ..train import adamw, adafactor, cosine_schedule
    from .mesh import make_mesh

    entry = get_arch(args.arch)
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev, 1), ("data", "model"))

    if entry.family == "lm":
        from ..models.transformer import (init_params, make_train_step,
                                          param_specs)
        cfg = entry.config(reduced=args.reduced or True)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = (adafactor if cfg.optimizer == "adafactor" else adamw)(
            cosine_schedule(3e-3, args.steps, max(1, args.steps // 10)))
        state = opt.init(params)
        step_fn = jax.jit(make_train_step(cfg, mesh, opt))
        pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq,
                             global_batch=args.batch, seed=0)
        start = 0
        if args.resume and args.ckpt_dir and \
                checkpoint.latest_step(args.ckpt_dir) is not None:
            start = checkpoint.latest_step(args.ckpt_dir)
            data = checkpoint.restore(args.ckpt_dir, start,
                                      dict(p=params, o=state))
            params, state = data["p"], data["o"]
            print(f"[train] resumed at step {start}")
        durations = []
        for step in range(start, args.steps):
            b = pipe.batch(step)
            t0 = time.perf_counter()
            params, state, loss = step_fn(
                params, state, dict(tokens=jnp.asarray(b["tokens"]),
                                    labels=jnp.asarray(b["labels"])))
            loss = float(loss)
            dt = time.perf_counter() - t0
            if durations and dt > 3.0 * float(np.median(durations)):
                print(f"[train] straggler flag at step {step}: "
                      f"{dt:.2f}s vs median {np.median(durations):.2f}s")
            durations.append(dt)
            print(f"[train] step {step} loss {loss:.4f} ({dt:.2f}s)")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt_dir, step + 1,
                                dict(p=params, o=state))
        return

    if entry.family == "gnn":
        from ..launch.specs import _GNN_MODS
        from ..graphs import erdos_renyi
        from ..models.gnn.common import batch_from_graph
        mod = _GNN_MODS[entry.arch_id]
        cfg = entry.config(reduced=True)
        rng = np.random.default_rng(0)
        g = erdos_renyi(200, 1200, seed=1)
        geometric = entry.arch_id in ("nequip", "equiformer-v2")
        out_kind = getattr(cfg, "out_kind", "node")
        labels = (np.zeros(1, np.float32) if out_kind == "graph"
                  else rng.integers(0, cfg.n_classes, g.n))
        batch = batch_from_graph(
            g, rng.normal(size=(g.n, cfg.d_feat)).astype(np.float32),
            labels=labels,
            pos=rng.normal(size=(g.n, 3)).astype(np.float32)
            if geometric else None)
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw(cosine_schedule(3e-3, args.steps, 2))
        state = opt.init(params)

        @jax.jit
        def step_fn(p, st, b):
            loss, grads = jax.value_and_grad(mod.loss_fn)(p, b, cfg)
            p, st = opt.apply(grads, st, p)
            return p, st, loss

        for step in range(args.steps):
            params, state, loss = step_fn(params, state, batch)
            print(f"[train] step {step} loss {float(loss):.4f}")
        return

    if entry.family == "recsys":
        from ..models.recsys import mind
        cfg = entry.config(reduced=True)
        rng = np.random.default_rng(0)
        B = args.batch
        batch = dict(
            hist_ids=jnp.asarray(rng.integers(0, cfg.n_items,
                                              (B, cfg.hist_len))),
            hist_mask=jnp.asarray(rng.random((B, cfg.hist_len)) > 0.2),
            profile_ids=jnp.asarray(rng.integers(0, cfg.n_profile, (B * 4,))),
            profile_bags=jnp.asarray(np.repeat(np.arange(B), 4)),
            pos_ids=jnp.asarray(rng.integers(0, cfg.n_items, (B,))),
            neg_ids=jnp.asarray(rng.integers(0, cfg.n_items,
                                             (B, cfg.n_neg))))
        params = mind.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw(cosine_schedule(1e-2, args.steps, 2))
        state = opt.init(params)

        @jax.jit
        def step_fn(p, st, b):
            loss, grads = jax.value_and_grad(mind.train_loss)(p, b, cfg,
                                                              mesh)
            p, st = opt.apply(grads, st, p)
            return p, st, loss

        for step in range(args.steps):
            params, state, loss = step_fn(params, state, batch)
            print(f"[train] step {step} loss {float(loss):.4f}")
        return

    raise SystemExit(f"--arch {args.arch}: use runtime.PsiDriver / "
                     "examples/distributed_dryrun_demo.py for the psi family")


if __name__ == "__main__":
    main()
