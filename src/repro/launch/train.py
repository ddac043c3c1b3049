"""Training driver — the end-to-end entry point.

Works unchanged from 1 CPU device (smoke configs) to a multi-pod TPU mesh:
the mesh is built from whatever devices exist (or --mesh-shape), sharding
rules come from sharding/specs.py, and the loop composes the deterministic
data pipeline, fault-tolerant checkpointing, and the straggler monitor.

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 20 --batch 8 --seq 64 --checkpoint-every 10
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import ARCHS, get_config, smoke_config
from repro.configs.base import RunConfig
from repro.data import SyntheticLM
from repro.kernels import use_pallas
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dp_axes_of, make_mesh
from repro.models import init_params
from repro.models.steps import train_step
from repro.models.transformer import DistContext
from repro.optim import adamw
from repro.runtime import StragglerMonitor
from repro.sharding import specs


def build_mesh(arg: str):
    if arg:
        dims = tuple(int(x) for x in arg.split(","))
    else:
        n = len(jax.devices())
        dims = (max(n // 1, 1), 1) if n == 1 else (n // 2, 2) if n % 2 == 0 else (n, 1)
    names = ("pod", "data", "model")[-len(dims):]
    return make_mesh(dims, names)


def init_sharded_params(cfg, mesh, *, ep_shards: int, seed: int):
    """Seeded params; on a mesh of several devices each leaf is created in
    place under ``specs.param_shardings`` (no full copy on one device)."""
    init = functools.partial(init_params, cfg, ep_shards=ep_shards)
    key = jax.random.PRNGKey(seed)
    p_sh = specs.param_shardings(jax.eval_shape(init, key), mesh) if mesh.size > 1 else None
    return jax.jit(init, out_shardings=p_sh)(key)


def main(argv=None):
    """Train; returns the loss of every step this run took."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--total-steps", type=int, default=0,
                    help="schedule horizon (defaults to --steps); set it when "
                         "running a partial leg of a longer run")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh-shape", default="", help="e.g. 4,2 => data=4,model=2")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)

    enable_compile_cache()
    # The Pallas kernels have no backward pass, so training traces the jnp
    # path whatever setting the caller holds.
    with use_pallas(False):
        return _train(args)


def _train(args):
    mesh = build_mesh(args.mesh_shape)
    tp = mesh.shape.get("model", 1)
    cfg0 = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg, ep_shards = specs.tp_adapt(cfg0, tp)
    dp_axes = dp_axes_of(mesh) or ("data",)
    dist = (
        DistContext(mesh=mesh, dp_axes=dp_axes, ep_shards=ep_shards)
        if np.prod(list(mesh.shape.values())) > 1
        else None
    )
    run = RunConfig(
        model=cfg,
        seq_len=args.seq,
        global_batch=args.batch,
        n_microbatches=args.microbatches,
        learning_rate=args.lr,
        warmup_steps=args.warmup,
        total_steps=args.total_steps or args.steps,
    )

    params = init_sharded_params(cfg, mesh, ep_shards=ep_shards, seed=args.seed)
    opt = adamw.init_state(params)

    data = SyntheticLM(
        vocab_size=cfg.vocab_size,
        seq_len=args.seq,
        global_batch=args.batch,
        seed=args.seed,
        frontend_tokens=cfg.frontend_tokens,
        frontend_dim=(cfg.frontend_dim or cfg.d_model) if cfg.frontend_tokens else 0,
    )
    # donated: at full width the params and Adam moments do not fit twice
    step_fn = jax.jit(functools.partial(train_step, cfg, run, dist=dist),
                      donate_argnums=(0, 1))

    ckpt = Checkpointer(args.checkpoint_dir) if args.checkpoint_dir else None
    start = 0
    if ckpt and ckpt.latest_step() is not None:
        start = ckpt.latest_step()
        blob = ckpt.restore(start, {"params": params, "opt": opt})
        params, opt = blob["params"], blob["opt"]
        print(f"[train] resumed from step {start}")

    mon = StragglerMonitor()
    tokens_per_step = args.batch * args.seq
    losses = []
    for step in range(start, args.steps):
        t0 = time.perf_counter()
        batch = {k: jax.numpy.asarray(v) for k, v in data.batch(step).items()}
        params, opt, metrics = step_fn(params, opt, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        dt = time.perf_counter() - t0
        mon.record(step, dt)
        if step % args.log_every == 0:
            print(
                f"[train] step {step} loss {loss:.4f} lr {float(metrics['lr']):.2e} "
                f"gnorm {float(metrics['grad_norm']):.2f} "
                f"{tokens_per_step / dt:.0f} tok/s",
                flush=True,
            )
        if ckpt and (step + 1) % args.checkpoint_every == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt}, block=False)
        if mon.should_mitigate:
            print("[train] straggler mitigation advised (persistent slow steps)")
    if ckpt:
        ckpt.save(args.steps, {"params": params, "opt": opt}, block=True)
    print(f"[train] done: final loss {loss:.4f}")
    return losses


if __name__ == "__main__":
    main()
