"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
        --reduced --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ck

Runs the real distributed train step (same code the dry-run lowers)
on whatever devices exist, with checkpoint/resume: if the checkpoint
dir holds a step, training resumes from it idempotently (the data
pipeline is a pure function of the step index).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-accum", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_arch
    from repro.data import lm_batch
    from repro.launch.mesh import make_local_topology
    from repro.models import lm as lm_mod
    from repro.train import (
        AdamWConfig, Checkpointer, TrainConfig, build_train_step,
        init_train_state,
    )

    mod = get_arch(args.arch)
    if getattr(mod, "FAMILY", "") != "lm":
        raise SystemExit("train driver currently targets the LM family; "
                         "use examples/gnn_train.py for GNNs")
    cfg = mod.make_config(reduced=args.reduced)
    topo = make_local_topology()
    tc = TrainConfig(
        adamw=AdamWConfig(lr=args.lr),
        microbatches=args.microbatches,
        compress_accum=args.compress_accum,
        warmup_steps=max(2, args.steps // 10),
        total_steps=args.steps,
    )

    params = lm_mod.init_params(jax.random.PRNGKey(args.seed), cfg)
    opt = init_train_state(params, tc)
    start = 0

    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ck and ck.latest_step() is not None:
        tree, man = ck.restore()
        params, opt, start = tree["params"], tree["opt"], man["step"]
        print(f"[train] resumed from step {start}")

    step_fn = jax.jit(
        build_train_step(lambda p, b: lm_mod.lm_loss(p, b, cfg, topo), tc)
    )

    t0 = time.time()
    for step in range(start, args.steps):
        batch = {
            k: jnp.asarray(v)
            for k, v in lm_batch(
                step, args.batch, args.seq, cfg.vocab, args.seed
            ).items()
        }
        params, opt, m = step_fn(params, opt, batch, jnp.int32(step))
        if step % 5 == 0 or step == args.steps - 1:
            print(
                f"[train] step {step:5d} loss={float(m['loss']):.4f} "
                f"gnorm={float(m['grad_norm']):.3f} "
                f"lr={float(m['lr']):.2e} "
                f"({(time.time()-t0):.1f}s)"
            )
        if ck and (step + 1) % args.ckpt_every == 0:
            ck.save_async(step + 1, {"params": params, "opt": opt})
    if ck:
        ck.save(args.steps, {"params": params, "opt": opt})
        print(f"[train] checkpointed step {args.steps}")


if __name__ == "__main__":
    main()
