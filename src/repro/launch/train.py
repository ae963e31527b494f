"""End-to-end training driver.

CPU-runnable at reduced configs (``--reduced``), mesh-ready at full
configs.  Composes: config -> model -> GSPMD shardings -> AdamW(+ZeRO-1,
bf16 grad compression) -> synthetic data pipeline -> fault-tolerant
checkpoint/restart loop with straggler monitoring.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
      --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs import ARCH_IDS, get_config
from repro.launch.cache import enable_compile_cache
from repro.data import DataConfig, SyntheticTokens
from repro.dist import partitioning
from repro.dist.partitioning import param_specs
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.runtime import RestartableLoop


def build_trainer(cfg, *, fusion_mode="stitched", lr=1e-3, total_steps=1000,
                  bf16_grads=False, mesh=None):
    mdl = build_model(cfg, fusion_mode=fusion_mode, remat=False)
    opt_cfg = optim.AdamWConfig(lr=lr, warmup_steps=min(20, total_steps // 10),
                                total_steps=total_steps,
                                bf16_grads=bf16_grads)
    step_fn = S.make_train_step(mdl, opt_cfg)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    def init_state(key):
        params = mdl.init(key)
        return {"params": params, "opt": optim.init(opt_cfg, params)}

    def train_step(state, batch):
        params, opt, metrics = jitted(state["params"], state["opt"], batch)
        train_step.last_metrics = jax.tree_util.tree_map(float, metrics)
        return {"params": params, "opt": opt}

    train_step.last_metrics = {}
    return mdl, init_state, train_step


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--fusion", default="stitched", choices=["stitched", "xla"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--bf16-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    mdl, init_state, train_step = build_trainer(
        cfg, fusion_mode=args.fusion, lr=args.lr, total_steps=args.steps,
        bf16_grads=args.bf16_grads)
    print(f"arch={cfg.name} params={mdl.param_count():,} "
          f"fusion={args.fusion}")

    data = SyntheticTokens(
        DataConfig(seed=args.seed, global_batch=args.batch, seq_len=args.seq),
        cfg)
    state = init_state(jax.random.PRNGKey(args.seed))

    loop = RestartableLoop(args.ckpt_dir, ckpt_every=args.ckpt_every)
    t0 = time.perf_counter()

    def on_step(step, state, dt, slow):
        m = train_step.last_metrics
        flag = " STRAGGLER" if slow else ""
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss={m.get('loss', float('nan')):.4f} "
                  f"gnorm={m.get('grad_norm', 0):.3f} "
                  f"lr={m.get('lr', 0):.2e} {dt*1e3:6.1f}ms{flag}",
                  flush=True)

    state, monitor = loop.run(state, data, train_step, args.steps,
                              on_step=on_step)
    print(f"done in {time.perf_counter()-t0:.1f}s; "
          f"stragglers flagged: {len(monitor.flagged_steps)}")


if __name__ == "__main__":
    main()
