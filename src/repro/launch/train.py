"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
        --steps 100 --batch 8 --seq 128 --ckpt /tmp/run1

Uses the full substrate: sharded state on the host mesh (or the production
mesh under forced host devices), resumable data pipeline, async checkpoints,
watchdog, retry-with-resume.
"""
from __future__ import annotations

import argparse
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, ARCH_IDS
from repro.data.pipeline import DataConfig
from repro.launch import (add_policy_args, policy_scope_from_args,
                          use_compile_cache)
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.base import activation_sharding
from repro.optim.adamw import AdamWConfig
from repro.optim.schedule import warmup_cosine
from repro.parallel import sharding as shd
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (smoke) config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    add_policy_args(ap)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    opt_cfg = AdamWConfig(lr=args.lr, use_master=True,
                          schedule=warmup_cosine(args.lr, 10, args.steps))

    state = steps_mod.init_train_state(jax.random.PRNGKey(args.seed), cfg,
                                       opt_cfg)
    pspecs = steps_mod.train_state_pspecs(cfg, opt_cfg, mesh)
    shardings = jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                             is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(state, shardings)

    step_fn = steps_mod.make_train_step(cfg, opt_cfg)
    # --policy/--site-policy scope the whole run: the step traces (and so
    # resolves its per-site policies) inside this scope.
    with policy_scope_from_args(args), mesh, activation_sharding(mesh):
        jit_step = jax.jit(step_fn, in_shardings=(shardings, None),
                           donate_argnums=(0,))

        loop = TrainLoop(
            cfg, TrainLoopConfig(total_steps=args.steps,
                                 checkpoint_every=args.ckpt_every,
                                 seed=args.seed),
            opt_cfg, jit_step, Path(args.ckpt),
            DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                       global_batch=args.batch, seed=args.seed),
            mesh=mesh)
        final = loop.run(state)
    losses = [h["loss"] for h in loop.history]
    if losses:
        print(f"first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")
    return final, loop


if __name__ == "__main__":
    main()
