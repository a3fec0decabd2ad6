"""Training driver: config-driven, fault-tolerant, resumable.

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-350m --smoke \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On this CPU container the driver runs reduced ("--smoke") configs on a small
host-device mesh; on a real slice the same code path runs the full config on
``make_production_mesh()``.  Auto-resumes from the newest valid checkpoint.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data.pipeline import StepWatchdog, SyntheticLM
from repro.distributed import checkpoint as ckpt
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models import ModelDims, get_arch, init_params, make_train_step
from repro.models.testing import reduced
from repro.optim import AdamWConfig, adamw


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["test", "prod"], default="test")
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="simulate a crash (fault-tolerance testing)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    mesh = (make_production_mesh() if args.mesh == "prod"
            else make_test_mesh())
    tp = mesh.devices.shape[-1] if shd.style_for(cfg) == "tp" else 1
    dims = ModelDims.create(cfg, tp=tp)
    specs = shd.make_specs(cfg, mesh, args.batch)
    opt = AdamWConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps)

    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(args.seed), dims)
        pspec = shd.param_specs(cfg, params)
        params = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, pspec)
        opt_state = adamw.init_state(opt, params)
        start_step = 0
        if args.ckpt_dir:
            try:
                state = {"params": params, "opt": opt_state}
                shards = {
                    "params": jax.tree.map(
                        lambda s: NamedSharding(mesh, s), pspec,
                        is_leaf=lambda x: isinstance(x, P)),
                    "opt": jax.tree.map(lambda a: a.sharding, opt_state),
                }
                state, start_step = ckpt.restore(args.ckpt_dir, state, shards)
                params, opt_state = state["params"], state["opt"]
                print(f"[train] resumed from step {start_step}")
            except FileNotFoundError:
                pass

        step_fn = jax.jit(make_train_step(cfg, dims, opt, specs=specs,
                                          accum_steps=args.accum),
                          donate_argnums=(0, 1))
        data = SyntheticLM(cfg, args.batch, args.seq, seed=args.seed)
        watchdog = StepWatchdog()
        losses = []
        pending = None
        for step in range(start_step, args.steps):
            if args.fail_at_step is not None and step == args.fail_at_step:
                raise RuntimeError(f"simulated failure at step {step}")
            batch = jax.tree.map(jnp.asarray, data.batch_at(step))
            t0 = time.time()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            slow = watchdog.record(step, dt)
            losses.append(loss)
            if step % args.log_every == 0 or slow:
                tag = " SLOW" if slow else ""
                print(f"[train] step={step} loss={loss:.4f} "
                      f"dt={dt*1e3:.1f}ms{tag}", flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                if pending is not None:
                    pending.join()
                pending = ckpt.save_async(
                    args.ckpt_dir, step + 1,
                    {"params": params, "opt": opt_state})
        if pending is not None:
            pending.join()
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, args.steps,
                      {"params": params, "opt": opt_state})
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "slow_steps": watchdog.slow_steps}


if __name__ == "__main__":
    main()
