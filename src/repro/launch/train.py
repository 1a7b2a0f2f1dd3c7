"""SPMD training driver: the DANA pod-round step on a real mesh.

This is the deployable path (DESIGN.md Sec. 2): pods are DANA's async
workers; one jitted step executes one master round.  On this CPU container
it runs the same program on a 1x1 host mesh (where the step is exactly
Nesterov, paper Alg. 5); on a pod/multi-pod it runs under the production
meshes validated by the dry-run.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --steps 100 --batch 8 --seq 128

Set --devices N to simulate an N-device host mesh (must be first arg; sets
XLA_FLAGS before jax initializes).
"""
import os
import sys

if "--devices" in sys.argv:                      # before any jax import
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={_n} "
                               + os.environ.get("XLA_FLAGS", ""))

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager, load_pytree, save_pytree
from ..configs import get_config
from ..core.schedules import Schedule
from ..data.synthetic import LMTask
from ..models.api import build_model
from .cache import enable_compile_cache
from .mesh import make_host_mesh
from .sharding import batch_specs, to_shardings
from .steps import TrainSettings, build_train_step, init_train_state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true", default=False)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--mesh", default=None,
                    help="'DxM' host mesh shape, e.g. 2x2 (needs --devices)")
    ap.add_argument("--pods", type=int, default=1,
                    help="leading pod axis size (async DANA workers)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        cfg = dataclasses.replace(cfg, vocab_size=min(cfg.vocab_size, 512))
    model = build_model(cfg)

    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        d, m = 1, 1
    if args.pods > 1:
        mesh = make_host_mesh((args.pods, d, m), ("pod", "data", "model"))
    else:
        mesh = make_host_mesh((d, m), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.name} "
          f"({_param_count(model)/1e6:.1f}M params)")

    settings = TrainSettings(lr=args.lr, momentum=args.momentum,
                             fsdp=d > 1)
    sched = Schedule(base_lr=args.lr, num_workers=max(args.pods, 1),
                     warmup_steps=args.warmup,
                     milestones=(int(0.8 * args.steps),))
    task = LMTask(vocab_size=cfg.vocab_size, seq_len=args.seq,
                  batch_size=args.batch, seed=args.seed)

    with mesh:
        step, state_specs, in_sh, out_sh = build_train_step(
            model, mesh, settings, sched, global_batch=args.batch)
        num_pods = mesh.shape.get("pod", 1)
        state = init_train_state(model, jax.random.PRNGKey(args.seed),
                                 num_pods)
        start = 0
        mgr = None
        if args.ckpt and not args.ckpt.endswith(".npz"):
            mgr = CheckpointManager(args.ckpt)     # directory mode
            restored, _ck_step = mgr.restore(state)
            if restored is not None:
                state, start = restored, int(restored["t"])
                print(f"resumed from {args.ckpt} at step {start}")
        elif args.ckpt and os.path.exists(args.ckpt):
            state = load_pytree(args.ckpt, like=state)
            start = int(state["t"])
            print(f"resumed from {args.ckpt} at step {start}")

        sample = {"tokens": task.batch(0, 0)}
        b_sh = to_shardings(mesh, batch_specs(cfg, mesh, sample))
        jstep = jax.jit(step, in_shardings=(in_sh[0], b_sh),
                        out_shardings=(out_sh[0], None),
                        donate_argnums=(0,))

        t0 = time.time()
        losses = []
        for i in range(start, args.steps):
            batch = {"tokens": task.batch(0, i)}
            state, metrics = jstep(state, batch)
            losses.append(float(metrics["loss"]))
            if (i + 1) % args.log_every == 0 or i + 1 == args.steps:
                dt = time.time() - t0
                tput = (i + 1 - start) * args.batch * args.seq / dt
                print(f"step {i+1:5d}  loss {losses[-1]:.4f}  "
                      f"lr {float(metrics['lr']):.2e}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"{tput:.0f} tok/s", flush=True)
            if args.ckpt and (i + 1) % args.ckpt_every == 0:
                if mgr is not None:
                    mgr.save(i + 1, state)
                    mgr.log_metrics(i + 1, loss=losses[-1],
                                    lr=float(metrics["lr"]))
                else:
                    save_pytree(args.ckpt, state)

        if args.ckpt:
            if mgr is not None:
                mgr.save(args.steps, state)
            else:
                save_pytree(args.ckpt, state)
        first = float(np.mean(losses[:5])) if len(losses) >= 5 else losses[0]
        last = float(np.mean(losses[-5:]))
        print(f"done: loss {first:.4f} -> {last:.4f} "
              f"({args.steps - start} steps, {time.time()-t0:.1f}s)")
        return first, last


def _param_count(model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


if __name__ == "__main__":
    main()
