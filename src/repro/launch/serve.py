"""Serving driver: batched prefill + greedy decode.

Demonstrates the inference path the decode_* dry-run shapes lower: one
prefill building per-layer caches, then a jitted single-token decode step
iterated with the KV/recurrent caches donated in place.

Observability (DESIGN.md §8): the run enables :mod:`repro.obs.metrics`
and, with ``--trace``, a :mod:`repro.obs.trace` tracer — so one serve run
emits one Perfetto-loadable timeline (prefill / per-token decode / plan
spans on the wall clock, plus the simulated per-resource timeline of the
collective the planner picked) and a one-line metrics digest at exit in
place of the old ad-hoc cache print.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, smoke_config
from repro.kernels import use_pallas
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dp_axes_of
from repro.launch.train import build_mesh, init_sharded_params
from repro.models import decode as dec
from repro.models.transformer import DistContext
from repro.obs import drift, health, metrics, trace
from repro.sharding import specs


@dataclasses.dataclass
class ServeResult:
    tokens: np.ndarray  # (B, N) generated ids; -1 after a sequence was shed
    prefill_logits: jax.Array  # (B, V) logits at the last prompt position
    prefill: jax.stages.Compiled  # the prefill program that produced them


def make_prompts(rng: np.random.Generator, vocab_size: int, batch: int,
                 prompt_len: int) -> np.ndarray:
    """Prompt ids in [2, vocab), the first draw of the run's seeded rng."""
    return rng.integers(2, vocab_size, size=(batch, prompt_len), dtype=np.int32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--mesh-shape", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--trace", default="", metavar="PATH",
        help="write a Chrome trace_event JSON of this run (open in Perfetto)",
    )
    ap.add_argument(
        "--metrics-out", default="", metavar="PATH",
        help="write the end-of-run metrics snapshot as JSON",
    )
    ap.add_argument(
        "--health-out", default="", metavar="PATH",
        help="write the link-health snapshot as JSON "
             "(inspect with python -m repro.obs.health --load PATH)",
    )
    ap.add_argument(
        "--degrade-at", type=int, default=-1, metavar="STEP",
        help="inject a synthetic bandwidth sag on --degrade-tier from this "
             "decode step on (degradation drill for the obs-health smoke)",
    )
    ap.add_argument(
        "--degrade-tier", default="dcn", metavar="TIER",
        help="tier of the active machine to sag (default: dcn)",
    )
    ap.add_argument(
        "--degrade-factor", type=float, default=10.0,
        help="measured/predicted ratio of the injected sag",
    )
    ap.add_argument(
        "--fail-at", type=int, default=-1, metavar="STEP",
        help="inject a host loss at this decode step (chaos drill): the "
             "serve loop degrades gracefully instead of dying",
    )
    ap.add_argument(
        "--fail-host", type=int, default=0, metavar="RANK",
        help="which host rank --fail-at loses (default: 0)",
    )
    ap.add_argument(
        "--fail-mode", default="shrink", choices=("shrink", "shed"),
        help="shrink: shrink_spec + re-register the active machine so "
             "per-step planning re-decides on the surviving mesh; shed: "
             "drop one in-flight sequence (batch B -> B-1) and keep going",
    )
    ap.add_argument(
        "--scenario", default="", metavar="PATH",
        help="drive failures from a scenario JSON "
             "(python -m repro.runtime.scenarios --out PATH): host_drop "
             "events map to --fail-mode handling at their step, link sags "
             "stream drift records into obs.health",
    )
    args = ap.parse_args(argv)

    enable_compile_cache()
    mesh = build_mesh(args.mesh_shape)
    # The kernels are compiled for the TPU only (elsewhere they would run
    # interpreted, which no deployment does), and a Mosaic kernel cannot be
    # partitioned over a mesh by the compiler.
    with use_pallas(jax.default_backend() == "tpu" and mesh.size == 1):
        return _serve(args, mesh)


def _serve(args, mesh) -> ServeResult:
    metrics.enable()
    tracer = trace.start(name="serve") if args.trace else None

    tp = mesh.shape.get("model", 1)
    cfg0 = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg, ep_shards = specs.tp_adapt(cfg0, tp)
    dist = (
        DistContext(mesh=mesh, dp_axes=dp_axes_of(mesh) or ("data",), ep_shards=ep_shards)
        if int(np.prod(list(mesh.shape.values()))) > 1
        else None
    )

    params = init_sharded_params(cfg, mesh, ep_shards=ep_shards, seed=args.seed)
    B, P_len, N = args.batch, args.prompt_len, args.new_tokens
    capacity = P_len + N
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(make_prompts(rng, cfg.vocab_size, B, P_len))
    frontend = None
    if cfg.frontend_tokens:
        fd = cfg.frontend_dim or cfg.d_model
        frontend = jnp.asarray(
            rng.standard_normal((B, cfg.frontend_tokens, fd), dtype=np.float32),
            jnp.bfloat16,
        )

    t0 = time.perf_counter()
    with trace.span("prefill.compile", batch=B, prompt_len=P_len):
        prefill_fn = jax.jit(
            functools.partial(dec.prefill, cfg, capacity=capacity, dist=dist)
        ).lower(params, prompts, frontend=frontend).compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    with trace.span("prefill", batch=B, prompt_len=P_len):
        logits, caches = prefill_fn(params, prompts, frontend=frontend)
        logits.block_until_ready()
    t_prefill = time.perf_counter() - t0
    prefill_logits = logits
    metrics.observe("serve.prefill.seconds", t_prefill)
    print(f"[serve] prefill {B}x{P_len} in {t_prefill:.2f}s "
          f"({B * P_len / t_prefill:.0f} tok/s), compiled in {t_compile:.2f}s")

    decode_fn = jax.jit(
        functools.partial(dec.decode_step, cfg, dist=dist),
        donate_argnums=(1,),
    )
    # Per-step planning: re-consult the model-driven strategy pick every
    # decode step (payload per chip grows with the live KV length, so the
    # pick can legitimately flip mid-generation).  The autotune plan cache
    # makes the repeat consultations microsecond probes — planner_speed in
    # benchmarks/ gates that this stays serving-loop affordable, and the
    # plan_cache.hit/miss counters (see the exit summary) replace the old
    # inline hit/miss print.
    from repro.comms.autotune import active_machine, select_allreduce_strategy
    from repro.core.machine import get_machine

    plan_shape = dict(mesh.shape)
    token_bytes = float(B * cfg.d_model) * 2  # bf16 activations per token
    # Degradation drill (--degrade-at): from that decode step on, per-step
    # link probes of --degrade-tier come back --degrade-factor x slower
    # than the active machine's model predicts.  The drift records stream
    # into obs.health; when the link degrades, the loop refits a degraded
    # variant from the sagged samples and re-registers it — the fingerprint
    # bump invalidates the plan cache, so the NEXT per-step plan call
    # re-decides against the degraded reality (DESIGN.md §10).
    degrade_machine = active_machine()
    degrade_spec = get_machine(degrade_machine) if args.degrade_at >= 0 else None
    degrade_probe_bytes = float(1 << 20)
    degrade_refit_done = False

    # Chaos drill (--fail-at / --scenario): host losses at decode steps.
    # In shrink mode each loss derives the surviving-mesh spec
    # (core.machine.shrink_spec) and re-registers it through
    # runtime.elastic.shrink_and_replan — fingerprint bump + generation
    # bump, so the NEXT per-step plan call re-decides on the mesh that
    # actually survives instead of replaying a stale pick (DESIGN.md §11).
    # In shed mode the loop sheds one in-flight sequence instead: caches
    # are sliced down to the shapes prefill would have produced at B-1
    # (via eval_shape — cache leaves don't share a batch axis position).
    drop_at = {}  # decode step -> [host ranks lost there]
    scenario_injector = None
    if args.scenario:
        from repro.runtime.scenarios import HOST_DROP, Scenario, ScenarioInjector

        sc = Scenario.load(args.scenario)
        for ev in sc.events:
            if ev.kind == HOST_DROP:
                drop_at.setdefault(ev.at, []).append(ev.host)
        scenario_injector = ScenarioInjector(
            sc, machine=degrade_machine, spec=get_machine(degrade_machine)
        )
        print(f"[serve] scenario {sc.name!r} (seed {sc.seed}): "
              f"{len(sc.events)} events")
    if args.fail_at >= 0:
        drop_at.setdefault(args.fail_at, []).append(args.fail_host)

    def handle_host_drop(step: int, host: int):
        nonlocal caches, tok
        metrics.inc("runtime.elastic.host_drops")
        iid = trace.begin_interval(f"host_drop:{host}", cat="elastic",
                                   step=step, mode=args.fail_mode)
        if args.fail_mode == "shrink":
            from repro.runtime.elastic import shrink_and_replan

            shrunk = shrink_and_replan(degrade_machine, [host])
            metrics.inc("runtime.elastic.replans")
            survivors = int(shrunk.facts["n_gpus"])
            print(f"[serve] host {host} lost at decode step {step}; "
                  f"shrunk {degrade_machine!r} to {survivors} ranks "
                  f"(fingerprint {shrunk.fingerprint[:12]}), replanning")
            trace.end_interval(f"host_drop:{host}", iid, cat="elastic",
                               survivors=survivors)
        else:
            new_b = int(tok.shape[0]) - 1
            if new_b < 1:
                print(f"[serve] host {host} lost at decode step {step}; "
                      f"batch already minimal, continuing")
                trace.end_interval(f"host_drop:{host}", iid, cat="elastic")
                return
            target = jax.eval_shape(
                lambda p, t, f: dec.prefill(
                    cfg, p, t, frontend=f, capacity=capacity, dist=dist
                ),
                params,
                jax.ShapeDtypeStruct((new_b, P_len), jnp.int32),
                None if frontend is None else jax.ShapeDtypeStruct(
                    (new_b,) + frontend.shape[1:], frontend.dtype
                ),
            )[1]

            def _slice(live, tgt):
                out = live
                for ax in range(out.ndim):
                    if out.shape[ax] != tgt.shape[ax]:
                        out = jax.lax.slice_in_dim(out, 0, tgt.shape[ax],
                                                   axis=ax)
                return out

            caches = jax.tree_util.tree_map(_slice, caches, target)
            tok = tok[:new_b]
            metrics.inc("runtime.elastic.shed")
            metrics.gauge("serve.batch.live", new_b)
            print(f"[serve] host {host} lost at decode step {step}; "
                  f"shed one sequence (batch {new_b + 1} -> {new_b})")
            trace.end_interval(f"host_drop:{host}", iid, cat="elastic",
                               batch=new_b)

    out_tokens = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    t0 = time.perf_counter()
    for i in range(N):
        with trace.span("decode.step", token=i):
            out_tokens.append(np.asarray(tok)[:, 0])
            if degrade_spec is not None:
                tier = degrade_spec.tiers[args.degrade_tier]
                t_model = float(tier.time(degrade_probe_bytes))
                sag = args.degrade_factor if i >= args.degrade_at else 1.0
                drift.record(degrade_machine, args.degrade_tier, "probe",
                             degrade_probe_bytes, t_model, sag * t_model)
                lk = health.monitor().link(degrade_machine, args.degrade_tier)
                if lk.state == health.DEGRADED and not degrade_refit_done:
                    degrade_refit_done = True
                    fit, _ = health.refit_degraded(
                        degrade_spec, lk, register_as=degrade_machine
                    )
                    print(f"[serve] link {lk.key} degraded at decode step {i} "
                          f"(detected in {lk.detection_records} records); "
                          f"refit beta x{fit.beta_scale:.1f}, replanning")
            if scenario_injector is not None:
                scenario_injector.feed_drift(i)
            for host in drop_at.pop(i, ()):
                handle_host_drop(i, host)
            with trace.span("plan"):
                collective = select_allreduce_strategy(
                    plan_shape, token_bytes * (P_len + i + 1)
                )
            logits, caches = decode_fn(params, caches, tok, jnp.int32(P_len + i))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        metrics.inc("serve.decode.tokens", int(tok.shape[0]))
    jax.block_until_ready(logits)
    t_dec = time.perf_counter() - t0
    metrics.observe("serve.decode.seconds", t_dec)
    print(f"[serve] per-step plan: {collective}")

    # Simulate the final pick through the event engine so the trace carries
    # the per-resource timeline + bottleneck attribution of what the plan
    # means in simulated time, not just the wall-clock spans around it.
    # (On a single-device mesh the selectors short-circuit without any
    # engine run, so this is also what guarantees resource tracks exist.)
    with trace.span("simulate"):
        from repro.comms.autotune import explain_bottleneck

        report = explain_bottleneck(None, token_bytes * (P_len + N), n_msgs=1)
    metrics.gauge("serve.simulated_makespan_s", report.makespan)

    # shed sequences stop producing tokens mid-run; pad their tail with -1
    # so the per-step rows still stack into one (B, N) matrix
    width = max(a.shape[0] for a in out_tokens)
    gen = np.stack(
        [np.pad(a, (0, width - a.shape[0]), constant_values=-1)
         for a in out_tokens],
        axis=1,
    )
    print(f"[serve] decoded {N} tokens x {B} seqs in {t_dec:.2f}s "
          f"({B * N / t_dec:.1f} tok/s)")
    print("[serve] sample generations (first 3 rows):")
    for row in gen[:3]:
        print("   ", row[:16].tolist())

    if tracer is not None:
        trace.stop()
        tracer.write(args.trace)
        print(f"[serve] trace written to {args.trace} "
              f"({len(tracer.events)} events)")
    if args.metrics_out:
        metrics.write(args.metrics_out)
        print(f"[serve] metrics written to {args.metrics_out}")
    if args.health_out:
        import json

        with open(args.health_out, "w") as f:
            json.dump(health.monitor().snapshot(), f, indent=2)
            f.write("\n")
        print(f"[serve] health written to {args.health_out}")
    print("[serve] metrics:",
          metrics.summary_line(prefixes=["serve.", "plan_cache.",
                                         "lowering_memo.", "engine.",
                                         "health.", "runtime.", "kernels."]))
    return ServeResult(tokens=gen, prefill_logits=prefill_logits, prefill=prefill_fn)


if __name__ == "__main__":
    main()
