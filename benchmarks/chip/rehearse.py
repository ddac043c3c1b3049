"""Compile a cell's programs for a described TPU v5e chip, without the chip,
and print what the compiler says each needs of the chip's memory.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py <cell> [--batch B]

Serving cells compile the prefill and the decode step at the traffic's
sizes; the training cell compiles the donated train step.  Nothing runs, so
this says nothing about time; it refuses what the chip's compiler refuses,
a program too large for the chip among it.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def _gib(n: int) -> str:
    return f"{n / 2 ** 30:.2f} GiB"


def report(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes \
        - m.alias_size_in_bytes
    print(f"{name}: arguments {_gib(m.argument_size_in_bytes)}, outputs "
          f"{_gib(m.output_size_in_bytes)}, aliased {_gib(m.alias_size_in_bytes)}, temp "
          f"{_gib(m.temp_size_in_bytes)}; sum {_gib(total)}, compiler's peak "
          f"{_gib(m.peak_memory_in_bytes)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("--batch", type=int, default=0, help="override the traffic's batch")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import use_pallas
    from repro.models import decode as dec
    from repro.models import init_params

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.cell(args.cell)
    t = dict(cell.traffic)
    if args.batch:
        t["batch"] = args.batch
    cfg = harness.program_config(cell.config)
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)
    params = on_chip(jax.eval_shape(functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    B = t.get("batch")
    if t["driver"] == "serve_static":
        P, N = t["prompt_len"], t["new_tokens"]
        prompts = on_chip(jax.ShapeDtypeStruct((B, P), jnp.int32))
        with use_pallas(True):
            pre = jax.jit(functools.partial(dec.prefill, cfg, capacity=P + N, dist=None))
            report(f"prefill B={B} P={P}", pre.lower(params, prompts).compile())
            caches = on_chip(jax.eval_shape(pre, params, prompts)[1])
            step = jax.jit(functools.partial(dec.decode_step, cfg, dist=None),
                           donate_argnums=(1,))
            tok = on_chip(jax.ShapeDtypeStruct((B, 1), jnp.int32))
            pos = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
            report(f"decode_step B={B} capacity={P + N}",
                   step.lower(params, caches, tok, pos).compile())
    elif t["driver"] == "train":
        from repro.configs.base import RunConfig
        from repro.models.steps import train_step
        from repro.optim import adamw

        run = RunConfig(model=cfg, seq_len=t["seq_len"], global_batch=B, n_microbatches=1)
        opt = on_chip(jax.eval_shape(adamw.init_state, params))
        tokens = on_chip(jax.ShapeDtypeStruct((B, t["seq_len"]), jnp.int32))
        with use_pallas(False):
            step = jax.jit(functools.partial(train_step, cfg, run, dist=None),
                           donate_argnums=(0, 1))
            report(f"train_step B={B} S={t['seq_len']}",
                   step.lower(params, opt, {"tokens": tokens}).compile())
    else:
        print(f"nothing to rehearse for driver {t['driver']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
