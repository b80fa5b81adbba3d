"""Distributed training driver.

On a real TPU pod this runs the sharded train step for an assigned arch with
checkpoint/restart; on CPU it runs the same code path on a small forced-host
mesh for validation:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch smollm_360m \
      --smoke --mesh 2,4 --steps 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import optimizer_for
from repro.models import init
from repro.training import (AsyncCheckpointer, DataConfig, TrainConfig,
                            init_train_state, latest_step, make_batch,
                            make_sharded_train_step, restore)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--mesh", default="",
                    help="comma dims, e.g. 2,4 -> (data, model)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
    else:
        dims = (jax.device_count(), 1)
    axes = ("data", "model")[:len(dims)] if len(dims) == 2 \
        else ("pod", "data", "model")
    mesh = make_mesh(dims, axes)
    print(f"mesh {dict(zip(axes, dims))}; model {cfg.name} "
          f"({cfg.param_count() / 1e6:.1f}M params)")

    tc = TrainConfig(optimizer=optimizer_for(cfg), remat="full")
    step_fn, params_sh, opt_sh = make_sharded_train_step(cfg, tc, mesh)

    with mesh:
        params = jax.jit(lambda k: init(cfg, k),
                         out_shardings=params_sh)(jax.random.key(0))
        opt_state = jax.jit(lambda p: init_train_state(cfg, tc, p),
                            out_shardings=opt_sh)(params)

        dc = DataConfig(vocab_size=cfg.vocab_size, batch_size=args.batch,
                        seq_len=args.seq)
        start = 0
        ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
        if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir):
            state, step, meta = restore(args.ckpt_dir, None,
                                        {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            start = meta["data_step"]
            print(f"resumed at data step {start}")

        t0 = time.time()
        for s in range(start, args.steps):
            batch = make_batch(dc, s)
            params, opt_state, m = step_fn(params, opt_state, batch)
            print(f"step {s}: loss {float(m['loss']):.4f} "
                  f"({time.time() - t0:.1f}s)", flush=True)
            if ckpt and (s + 1) % 20 == 0:
                ckpt.save_async(s + 1, {"params": params, "opt": opt_state},
                                metadata={"data_step": s + 1})
        if ckpt:
            ckpt.wait()


if __name__ == "__main__":
    main()
