"""Production training launcher.

Wires the full substrate: config → mesh → sharded init (or elastic
checkpoint restore) → jitted train_step with donation → data pipeline →
periodic async checkpoints.  ``--tiny`` runs reduced configs end to end on
the CPU; the full configs need a multi-chip mesh, which the dry-run compiles
for but which no chip run has exercised.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --tiny \
        --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.distributed import sharding as shd
from repro.distributed.context import use_rules
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import transformer
from repro.training import (DataConfig, OptConfig, TokenDataset, TrainConfig,
                            checkpoint, make_train_step)
from repro.training.optimizer import init_opt_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b",
                    choices=list(configs.ARCH_NAMES))
    ap.add_argument("--tiny", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"],
                    help="host = whatever devices exist; single/multi = "
                         "production meshes (needs 256/512 devices)")
    args = ap.parse_args()

    cfg = (configs.get_tiny_config(args.arch) if args.tiny
           else configs.get_config(args.arch))
    tcfg = TrainConfig(
        opt=OptConfig(total_steps=args.steps),
        remat=args.remat, grad_accum=args.grad_accum,
        compress_grads=args.compress_grads)

    if args.mesh == "host":
        n = len(jax.devices())
        mesh = make_mesh((1, n), ("data", "model")) if n > 1 else \
            make_mesh((1, 1), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    shape = configs.Shape("train", "train", args.seq_len, args.global_batch)
    rules = shd.logical_rules(cfg, shape, mesh)
    data = TokenDataset(DataConfig(args.seq_len, args.global_batch), cfg)

    with use_rules(mesh, rules):
        p_shape = jax.eval_shape(
            functools.partial(transformer.init_params, cfg=cfg,
                              dtype=jnp.float32), jax.random.PRNGKey(0))
        p_spec = shd.param_specs(p_shape, cfg, mesh)
        p_shardings = shd.as_shardings(p_spec, mesh)

        start = 0
        if args.ckpt_dir and checkpoint.latest_step(args.ckpt_dir):
            start, state = checkpoint.load(args.ckpt_dir)
            params, opt = state["params"], state["opt"]
            params = jax.tree.map(jax.device_put, params, p_shardings)
            print(f"elastic-resumed step {start} onto "
                  f"{mesh.devices.size}-device mesh")
        else:
            params = jax.jit(
                functools.partial(transformer.init_params, cfg=cfg,
                                  dtype=jnp.float32),
                out_shardings=p_shardings)(jax.random.PRNGKey(0))
            opt = init_opt_state(params, tcfg.opt)

        step_fn = jax.jit(make_train_step(cfg, tcfg),
                          donate_argnums=(0, 1))
        n_params = sum(x.size for x in jax.tree.leaves(params))
        print(f"{cfg.name}: {n_params/1e6:.1f}M params on "
              f"{mesh.devices.size} device(s), {args.steps} steps")
        t0 = time.time()
        for i in range(start, args.steps):
            params, opt, m = step_fn(params, opt, data.batch_at(i))
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:5d} loss {float(m['loss']):.4f} "
                      f"lr {float(m['lr']):.2e} "
                      f"({time.time()-t0:.1f}s)", flush=True)
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt_dir, i + 1,
                                {"params": params, "opt": opt},
                                blocking=False)
        if args.ckpt_dir:
            checkpoint.save(args.ckpt_dir, args.steps,
                            {"params": params, "opt": opt})


if __name__ == "__main__":
    main()
