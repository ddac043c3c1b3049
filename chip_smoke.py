"""Bring-up check on the TPU: the repo's own entry points, in one process.

    python chip_smoke.py             # one chip: serve, train, transfer
    python chip_smoke.py --chips 4   # four chips: collectives, ping-pong,
                                     # sharded serve and train

llama3.2-1b runs at its published widths with seeded random weights,
through ``repro.launch.serve.main`` and ``repro.launch.train.main``.  Each
phase prints its wall time, its compile time, the device's peak memory so
far and the checks it made.  Only when every phase passed does the last
line of standard output give the device, as one JSON object.  Without a
TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "llama3.2-1b"
SEED = 0
# Prefill logits of the kernel path against the jnp path, both bf16 models
# on the same params and prompts.  The jnp path rounds attention
# probabilities to bf16 (relative 2^-9) where the kernel keeps f32; over 16
# residual layers that gave max |d| = 1.1% and mean |d| = 0.2% of max |ref|
# at 2 and 4 layers in interpret mode on the CPU.  A wrong kernel (mask,
# scale, GQA map) moves logits by the order of max |ref| itself.
LOGIT_MAX_TOL = 5e-2  # max |d| / max |ref|
LOGIT_MEAN_TOL = 1e-2  # mean |d| / max |ref|
# Step-0 loss of random weights: cross-entropy of a near-uniform guess.
LOSS0_REL_TOL = 0.05  # |loss0 - ln V| / ln V
# A sharded step against one device on the same params and batch.
SHARDED_LOSS_TOL = 2e-2  # absolute, nats

_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    # XLA's compile alone: trace and lowering events nest, and would count twice
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


class Checks:
    def __init__(self, phase: str):
        self.phase, self.failed = phase, []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"[{self.phase}] check {'ok' if ok else 'FAILED'}: {what}", flush=True)
        if not ok:
            self.failed.append(what)


def run_phase(name: str, fn, devices) -> bool:
    check = Checks(name)
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    try:
        fn(check)
    except Exception:  # report the phase as failed and go on to the next
        traceback.print_exc()
        check(False, "phase raised (traceback on stderr)")
    wall = time.perf_counter() - t0
    gc.collect()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    print(f"[{name}] wall {wall:.2f} s, backend compile {_COMPILE_S[0] - c0:.2f} s, "
          f"peak_bytes_in_use so far {peaks}", flush=True)
    return not check.failed


def check_logits(check, what: str, got, ref) -> None:
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    scale = float(np.abs(ref).max())
    d = np.abs(got - ref)
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    check(
        bool(np.isfinite(got).all())
        and d.max() <= LOGIT_MAX_TOL * scale and d.mean() <= LOGIT_MEAN_TOL * scale,
        f"{what}: max|d| {d.max():.4g}, mean|d| {d.mean():.4g} against max|ref| "
        f"{scale:.4g} (limits {LOGIT_MAX_TOL:g}, {LOGIT_MEAN_TOL:g} of it); "
        f"argmax agreement {agree:.3f}",
    )


def one_device_prefill(cfg, batch: int, prompt_len: int, new_tokens: int):
    """serve.main's prefill logits recomputed on one device by the jnp path."""
    import jax

    from repro.kernels import use_pallas
    from repro.launch import serve
    from repro.launch.train import build_mesh, init_sharded_params
    from repro.models import decode as dec

    params = init_sharded_params(cfg, build_mesh("1,1"), ep_shards=1, seed=SEED)
    prompts = serve.make_prompts(np.random.default_rng(SEED), cfg.vocab_size, batch,
                                 prompt_len)
    with use_pallas(False):
        fn = jax.jit(functools.partial(dec.prefill, cfg, capacity=prompt_len + new_tokens))
        return np.asarray(fn(params, prompts)[0], np.float32)


def serve_argv(batch: int, prompt_len: int, new_tokens: int, mesh: str):
    return ["--arch", ARCH, "--batch", str(batch), "--prompt-len", str(prompt_len),
            "--new-tokens", str(new_tokens), "--seed", str(SEED), "--mesh-shape", mesh]


# -- one chip -----------------------------------------------------------------


def phase_serve(check) -> None:
    from repro.configs import get_config
    from repro.launch import serve

    B, P, N = 8, 1024, 64
    cfg = get_config(ARCH)
    res = serve.main(serve_argv(B, P, N, "1,1"))
    n = res.prefill.as_text().count("tpu_custom_call")
    got, tok = np.asarray(res.prefill_logits, np.float32), res.tokens
    del res
    check(n > 0, f"compiled prefill holds {n} tpu_custom_call (flash attention on)")
    check_logits(check, "prefill logits, kernel vs jnp path", got,
                 one_device_prefill(cfg, B, P, N))
    check(tok.shape == (B, N) and bool(((tok >= 0) & (tok < cfg.vocab_size)).all()),
          f"{tok.shape} generated tokens in [0, {cfg.vocab_size})")


def phase_train(check) -> None:
    from repro.configs import get_config
    from repro.launch import train

    losses = train.main(["--arch", ARCH, "--batch", "1", "--seq", "2048", "--steps", "3",
                         "--warmup", "1", "--seed", str(SEED)])
    ln_v = math.log(get_config(ARCH).vocab_size)
    check(len(losses) == 3 and all(math.isfinite(x) for x in losses),
          f"3 donated steps at B=1 x S=2048, losses {losses}")
    check(abs(losses[0] - ln_v) <= LOSS0_REL_TOL * ln_v,
          f"step-0 loss {losses[0]:.4f} within {LOSS0_REL_TOL:g} of ln(V) = {ln_v:.4f}")


def fit_line(name: str, res) -> str:
    f = res.fitted
    return (f"{name}: alpha {f.alpha:.4e} s, beta {f.beta:.4e} s/B "
            f"({1 / f.beta / 1e9 if f.beta > 0 else float('inf'):.3f} GB/s); "
            f"samples {[(s, float(t)) for s, t in zip(res.sizes, res.times)]}")


def phase_transfer(check) -> None:
    import jax

    from repro.core.benchmark import bench_device_to_host, bench_host_device_roundtrip

    kind = jax.devices()[0].device_kind
    for name, res in (("host->device", bench_host_device_roundtrip()),
                      ("device->host", bench_device_to_host())):
        print(f"[transfer] {kind} {fit_line(name, res)}", flush=True)
        check(all(t > 0 for t in res.times) and res.fitted.beta > 0,
              f"{name} postal fit on {kind} has a positive slope")


# -- four chips -----------------------------------------------------------------


def phase_collectives(check) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from repro import comms
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("x", "y"))
    ring = make_mesh((4,), ("r",))  # the same four chips as one ring
    rng = np.random.default_rng(SEED)
    x = jnp.asarray(rng.standard_normal((4, 64, 128)), jnp.float32)
    blocks = jnp.asarray(rng.standard_normal((4, 4, 256)), jnp.float32)

    def lax_ref(m, spec_in, spec_out, body, a):
        return shard_map(body, mesh=m, in_specs=spec_in, out_specs=spec_out,
                         check_vma=False)(a)

    xy = P(("x", "y"))
    psum_xy = lax_ref(mesh, xy, xy, lambda v: lax.psum(v, ("x", "y")), x)
    a2a = lax_ref(mesh, xy, xy, lambda v: lax.all_to_all(
        v, ("x", "y"), split_axis=1, concat_axis=0, tiled=True).reshape(v.shape), blocks)
    total = np.broadcast_to(np.asarray(x).sum(0), x.shape)
    transposed = np.asarray(blocks).swapaxes(0, 1)
    # (name, result, jax.lax reference, numpy result)
    cases = [
        ("allreduce_flat", comms.allreduce_flat(x, mesh, ("x", "y")), psum_xy, total),
        ("allreduce_hierarchical", comms.allreduce_hierarchical(x, mesh, "x", ("y",)),
         psum_xy, total),
        ("allreduce_ring", comms.allreduce_ring(x, ring, "r"),
         lax_ref(ring, P("r"), P("r"), lambda v: lax.psum(v, "r"), x), total),
        ("all_gather_axis", comms.all_gather_axis(x[:2], mesh, "y", dim=0),
         lax_ref(mesh, P("y"), P(), lambda v: lax.all_gather(v, "y", axis=0, tiled=True),
                 x[:2]), np.asarray(x[:2])),
        ("alltoall_direct", comms.alltoall_direct(blocks, mesh, ("x", "y")), a2a, transposed),
        ("alltoall_hierarchical", comms.alltoall_hierarchical(blocks, mesh, "x", "y"), a2a,
         transposed),
        ("ring_shift", comms.ring_shift(x, ring, "r", 1),
         lax_ref(ring, P("r"), P("r"),
                 lambda v: lax.ppermute(v, "r", [(i, (i + 1) % 4) for i in range(4)]), x),
         np.roll(np.asarray(x), 1, axis=0)),
    ]
    kind = jax.devices()[0].device_kind
    for name, got, ref, want in cases:
        got, ref = np.asarray(got), np.asarray(ref)
        d = float(np.abs(got - ref).max())
        check(got.shape == ref.shape and d <= 1e-5 * float(np.abs(ref).max())
              and np.allclose(ref, want, rtol=1e-5, atol=1e-4),
              f"{name} on {kind} x4 equals its jax.lax reference (max|d| {d:.3g}), "
              f"which equals the numpy result")


def phase_pingpong(check) -> None:
    import jax

    from repro.comms.autotune import select_transfer_path
    from repro.core.machine import plan_costs
    from repro.core.benchmark import (
        bench_device_pingpong,
        bench_device_to_host,
        bench_host_device_roundtrip,
        bench_staged_pingpong,
        bench_transfer,
        spec_from_measurements,
    )

    a, b = jax.devices()[:2]
    sizes = (1 << 12, 1 << 16, 1 << 20, 1 << 23)
    direct = bench_device_pingpong(a, b, sizes)
    staged = bench_staged_pingpong(a, b, sizes)
    h2d = bench_host_device_roundtrip(sizes)
    d2h = bench_device_to_host(sizes)
    # one host: the staged path's "network" hop is a host memory copy
    host = bench_transfer(lambda s: (np.ones(s, np.uint8), np.empty(s, np.uint8)),
                          lambda bufs: np.copyto(bufs[1], bufs[0]), sizes)
    for name, res in (("direct", direct), ("staged", staged), ("copy_h2d", h2d),
                      ("copy_d2h", d2h), ("host_copy", host)):
        print(f"[pingpong] {a.device_kind} {a.id}->{b.id} {fit_line(name, res)}", flush=True)
        check(all(t > 0 for t in res.times), f"{name} samples are positive")
    spec = spec_from_measurements("v5e_pingpong", direct, staged_net=host, copy_d2h=d2h,
                                  copy_h2d=h2d, register=False)
    for i, s in enumerate(sizes):
        pick = select_transfer_path(spec, float(s))
        predicted = {k: f"{v:.4e}" for k, v in plan_costs(spec, float(s), 1).items()}
        won = "direct" if direct.times[i] <= staged.times[i] else "staged"
        print(f"[pingpong] {s} B: planner picks {pick} (predicted s {predicted}); "
              f"measured one-way direct {direct.times[i]:.4e} s, staged "
              f"{staged.times[i]:.4e} s: {won} is faster", flush=True)
        check(pick in predicted, f"{s} B: pick {pick} is a declared plan")


def phase_serve_mesh(check) -> None:
    from repro.configs import get_config
    from repro.launch import serve
    from repro.sharding import specs

    B, P, N = 8, 1024, 16
    cfg, _ = specs.tp_adapt(get_config(ARCH), 2)
    res = serve.main(serve_argv(B, P, N, "2,2"))
    got = np.asarray(res.prefill_logits, np.float32)
    tok = res.tokens
    del res
    check_logits(check, "prefill logits, mesh 2,2 vs one device", got,
                 one_device_prefill(cfg, B, P, N))
    check(tok.shape == (B, N) and bool(((tok >= 0) & (tok < cfg.vocab_size)).all()),
          f"{tok.shape} generated tokens in [0, {cfg.vocab_size})")


def phase_train_mesh(check) -> None:
    import jax

    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.launch import train
    from repro.models.steps import next_token_loss
    from repro.sharding import specs

    B, S = 4, 1024
    losses = train.main(["--arch", ARCH, "--batch", str(B), "--seq", str(S), "--steps", "3",
                         "--warmup", "1", "--seed", str(SEED), "--mesh-shape", "2,2"])
    check(len(losses) == 3 and all(math.isfinite(x) for x in losses),
          f"3 steps on mesh 2,2 at B={B} x S={S}, losses {losses}")
    cfg, _ = specs.tp_adapt(get_config(ARCH), 2)
    params = train.init_sharded_params(cfg, train.build_mesh("1,1"), ep_shards=1, seed=SEED)
    tokens = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=S, global_batch=B,
                         seed=SEED).batch(0)["tokens"]
    ref = float(jax.jit(lambda p, t: next_token_loss(cfg, p, t)[0])(params, tokens))
    check(abs(losses[0] - ref) <= SHARDED_LOSS_TOL,
          f"step-0 loss {losses[0]:.5f} vs one device's next_token_loss {ref:.5f} "
          f"(limit {SHARDED_LOSS_TOL:g})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found {devices}",
              file=sys.stderr)
        return 2
    devices = devices[:args.chips]

    from repro.launch.compile_cache import enable_compile_cache

    print(f"[chip_smoke] {devices[0].device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if args.chips == 4:
        phases = [("collectives", phase_collectives), ("pingpong", phase_pingpong),
                  ("serve_mesh", phase_serve_mesh), ("train_mesh", phase_train_mesh)]
    else:
        phases = [("serve", phase_serve), ("train", phase_train),
                  ("transfer", phase_transfer)]
    results = [run_phase(name, fn, devices) for name, fn in phases]
    failed = [name for (name, _), ok in zip(phases, results) if not ok]
    if failed:
        print(f"[chip_smoke] failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
