import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST run before any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh — (16,16) "data","model" single-pod or
     (2,16,16) "pod","data","model" two-pod;
  2. adapts the architecture config for the TP width (KV expansion,
     ep_shards — sharding/specs.tp_adapt);
  3. constructs abstract (ShapeDtypeStruct) params / optimizer state /
     caches / batch — nothing is allocated;
  4. jits the step (train / prefill / decode per the shape kind) with full
     in/out shardings and donation, ``.lower().compile()``;
  5. records memory_analysis(), cost_analysis(), and per-kind collective
     bytes parsed from the compiled HLO (ICI vs DCN attributed by replica
     group membership) into results/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--tag variantname ...]
"""
import argparse
import functools
import json
import re
import sys
import time
import traceback


from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.obs import trace as obs_trace


# --------------------------------------------------------------------------


def build_cell(arch: str, shape_name: str, mesh_kind: str, variant: dict):
    """Returns (jitted fn, abstract args tuple, meta dict) for one cell."""
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_config
    from repro.configs.base import RunConfig
    from repro.launch.mesh import dp_axes_of, make_production_mesh
    from repro.models import decode as dec
    from repro.models import init_params, steps
    from repro.models.transformer import DistContext
    from repro.optim import adamw
    from repro.sharding import specs

    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    tp = mesh.shape["model"]
    cfg0 = get_config(arch)
    cfg, ep_shards = specs.tp_adapt(cfg0, tp)

    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return None, None, {
            "skipped": "pure full-attention arch: 500k dense-KV decode "
            "excluded per spec (DESIGN.md §Arch-applicability)"
        }

    if variant.get("wkv_chunk"):
        import dataclasses as _dc

        cfg = _dc.replace(cfg, wkv_chunk=int(variant["wkv_chunk"]))

    dp_axes = dp_axes_of(mesh)
    ep_axes = ("model",)
    if variant.get("serve_layout"):
        # serving layout: experts spread over (data x model) — no FSDP
        # weight gathers at decode; dispatch a2a spans both axes
        ep_axes = ("data", "model")
        if cfg.is_moe:
            total = 1
            for a in ep_axes:
                total *= mesh.shape[a]
            ep_shards = total // cfg.n_experts if total % cfg.n_experts == 0 else ep_shards
        variant = dict(variant, no_fsdp=True)
        if variant.get("moe_strategy", "direct") == "auto" and cfg.is_moe:
            from repro.comms.autotune import select_moe_dispatch_strategy
            from repro.models.moe import capacity as moe_capacity

            toks = shape.global_batch * (
                shape.seq_len if shape.kind != "decode" else 1
            )
            total = 1
            for a in ep_axes:
                total *= mesh.shape[a]
            tslice = max(1, -(-toks // total))
            bucket = moe_capacity(cfg, tslice) * cfg.d_model * 2
            variant = dict(
                variant,
                moe_strategy=select_moe_dispatch_strategy(
                    dict(mesh.shape), ep_axes, float(bucket)
                ),
            )
    dist = DistContext(
        mesh=mesh,
        dp_axes=dp_axes,
        model_axis="model",
        ep_shards=ep_shards,
        moe_strategy=variant.get("moe_strategy", "direct"),
        a2a_chunks=int(variant.get("a2a_chunks", 1)),
        ep_axes=ep_axes,
    )
    fsdp = not variant.get("no_fsdp", False)
    fsdp_axes = tuple(variant.get("fsdp_axes", "data").split("+"))
    remat = not variant.get("no_remat", False)

    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(
        functools.partial(init_params, cfg, ep_shards=ep_shards), key
    )
    p_sh = specs.param_shardings(
        params_shape, mesh, fsdp=fsdp, fsdp_axes=fsdp_axes, ep_axes=ep_axes
    )

    B, S = shape.global_batch, shape.seq_len
    tok_sh = specs.batch_sharding(mesh, B, 2, dp_axes)
    meta = {
        "arch": arch,
        "deploy_kv_heads": cfg.n_kv_heads,
        "ep_shards": ep_shards,
        "ep_axes": list(ep_axes),
        "moe_strategy_resolved": dist.moe_strategy,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }

    frontend_shape = None
    if cfg.frontend_tokens:
        fd = cfg.frontend_dim or cfg.d_model
        frontend_shape = jax.ShapeDtypeStruct((B, cfg.frontend_tokens, fd), jnp.bfloat16)

    if shape.kind == "train":
        run = RunConfig(
            model=cfg,
            seq_len=S,
            global_batch=B,
            n_microbatches=int(variant.get("microbatches", 1)),
            fsdp=fsdp,
            remat=remat,
            remat_policy=variant.get("remat_policy", "block"),
            grad_accum_dtype=variant.get("grad_accum_dtype", "float32"),
        )
        opt_shape = jax.eval_shape(adamw.init_state, params_shape)
        # ZeRO-1 over the pod axis: sharding the optimizer moments over
        # (pod, data) makes XLA reduce-scatter gradients across pods and
        # all-gather only bf16 params back — the paper's "split the slow
        # tier over every agent" via sharding alone.
        opt_fsdp_axes = tuple(
            variant.get("opt_fsdp_axes", "+".join(fsdp_axes)).split("+")
        )
        o_sh = specs.opt_shardings(
            params_shape, mesh, fsdp=True, fsdp_axes=opt_fsdp_axes, ep_axes=ep_axes
        )
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        batch_sh = {"tokens": tok_sh}
        if frontend_shape is not None:
            batch["frontend"] = frontend_shape
            batch_sh["frontend"] = specs.batch_sharding(mesh, B, 3, dp_axes)

        def fn(p, o, b):
            return steps.train_step(cfg, run, p, o, b, dist=dist)

        jf = jax.jit(
            fn,
            in_shardings=(p_sh, o_sh, batch_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1),
        )
        args = (params_shape, opt_shape, batch)
        meta["tokens_global"] = B * S
        meta["step_kind"] = "train"
        return jf, args, meta

    if shape.kind == "prefill":
        caches_shape = jax.eval_shape(lambda: dec.init_caches(cfg, B, S))
        c_sh = specs.cache_shardings(caches_shape, mesh, dp_axes=dp_axes)

        def fn(p, t, f=None):
            return steps.prefill_step(cfg, p, t, frontend=f, capacity=S, dist=dist)

        in_sh = [p_sh, tok_sh]
        args = [params_shape, jax.ShapeDtypeStruct((B, S), jnp.int32)]
        if frontend_shape is not None:
            in_sh.append(specs.batch_sharding(mesh, B, 3, dp_axes))
            args.append(frontend_shape)
        jf = jax.jit(fn, in_shardings=tuple(in_sh), out_shardings=(None, c_sh))
        meta["tokens_global"] = B * S
        meta["step_kind"] = "prefill"
        return jf, tuple(args), meta

    # decode: one new token against a seq_len-deep cache
    caches_shape = jax.eval_shape(lambda: dec.init_caches(cfg, B, S))
    c_sh = specs.cache_shardings(caches_shape, mesh, dp_axes=dp_axes)
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(p, c, t, q):
        return steps.decode_step(cfg, p, c, t, q, dist=dist)

    jf = jax.jit(
        fn,
        in_shardings=(p_sh, c_sh, specs.batch_sharding(mesh, B, 2, dp_axes), None),
        out_shardings=(None, c_sh),
        donate_argnums=(1,),
    )
    meta["tokens_global"] = B
    meta["step_kind"] = "decode"
    return jf, (params_shape, caches_shape, token, pos), meta


def run_cell(arch: str, shape_name: str, mesh_kind: str, variant: dict, outdir: str):
    import jax

    tag = variant.get("tag", "baseline")
    cell_id = f"{arch}__{shape_name}__{mesh_kind}__{tag}"
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": {k: v for k, v in variant.items() if k != "tag"}, "tag": tag,
    }
    t0 = time.time()
    try:
        with obs_trace.span("dryrun.build", cell=cell_id):
            jf, args, meta = build_cell(arch, shape_name, mesh_kind, variant)
        rec.update(meta)
        if jf is None:
            rec["ok"] = "skipped"
        else:
            with obs_trace.span("dryrun.lower", cell=cell_id):
                lowered = jf.lower(*args)
            with obs_trace.span("dryrun.compile", cell=cell_id):
                compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 1)
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "generated_code_bytes": ma.generated_code_size_in_bytes,
            }
            ca = compiled.cost_analysis() or {}
            rec["cost"] = {
                "flops": ca.get("flops", 0.0),
                "bytes_accessed": ca.get("bytes accessed", 0.0),
            }
            hlo = compiled.as_text()
            rec["hlo_chars"] = len(hlo)
            # persist the HLO so hlo_analysis can be re-run offline
            # (benchmarks/reanalyze.py) without recompiling the cell
            os.makedirs(outdir, exist_ok=True)
            import gzip

            with gzip.open(os.path.join(outdir, cell_id + ".hlo.gz"), "wt") as zf:
                zf.write(hlo)
            hc = hlo_analyze(hlo, chips_per_pod=256)
            rec["hlo_cost"] = {
                "dot_flops": hc.dot_flops,
                "hbm_bytes": hc.hbm_bytes,
                "collectives": hc.collectives,
                "collective_ici_bytes": hc.collective_ici_total(),
                "collective_dcn_bytes": hc.collective_dcn_total(),
            }
            rec["ok"] = True
    except (ValueError, TypeError, KeyError, AttributeError, RuntimeError,
            NotImplementedError, OSError) as e:
        # record the failure, keep sweeping: shape/sharding mistakes surface
        # as ValueError/TypeError, XLA compile failures and OOM as
        # RuntimeError (XlaRuntimeError subclasses it), HLO persistence as
        # OSError — anything else is a harness bug and should crash loudly
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
        print(f"[dryrun] {cell_id}: failed with {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
    rec["total_s"] = round(time.time() - t0, 1)
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, cell_id + ".json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec.get("ok")
    print(f"[dryrun] {cell_id}: ok={status} ({rec['total_s']}s)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="sweep every cell")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--moe-strategy", default="direct")
    ap.add_argument("--a2a-chunks", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--fsdp-axes", default="data", help="e.g. pod+data")
    ap.add_argument("--opt-fsdp-axes", default="", help="optimizer-state FSDP axes (ZeRO-1 over pod)")
    ap.add_argument("--grad-accum-dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--remat-policy", default="block", choices=["block", "dots", "none"])
    ap.add_argument("--serve-layout", action="store_true")
    ap.add_argument("--wkv-chunk", type=int, default=0)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument(
        "--trace", default="", metavar="PATH",
        help="write a Chrome trace_event JSON of the sweep (build/lower/"
             "compile spans per cell; open in Perfetto)",
    )
    args = ap.parse_args()

    tracer = obs_trace.start(name="dryrun") if args.trace else None

    from repro.configs import ARCHS, SHAPES

    variant = {
        "tag": args.tag,
        "moe_strategy": args.moe_strategy,
        "a2a_chunks": args.a2a_chunks,
        "microbatches": args.microbatches,
        "no_fsdp": args.no_fsdp,
        "no_remat": args.no_remat,
        "fsdp_axes": args.fsdp_axes,
        "opt_fsdp_axes": args.opt_fsdp_axes or args.fsdp_axes,
        "grad_accum_dtype": args.grad_accum_dtype,
        "remat_policy": args.remat_policy,
        "serve_layout": bool(args.serve_layout),
        "wkv_chunk": args.wkv_chunk,
    }
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                cell_id = f"{arch}__{shape}__{mk}__{args.tag}"
                path = os.path.join(args.out, cell_id + ".json")
                if args.skip_existing and os.path.exists(path):
                    try:
                        old = json.load(open(path))
                        if old.get("ok") in (True, "skipped"):
                            print(f"[dryrun] {cell_id}: cached ok={old['ok']}")
                            n_ok += 1
                            continue
                    except (OSError, ValueError, AttributeError) as e:
                        # unreadable/truncated cache record — re-run the cell
                        # (json decode errors are ValueError subclasses)
                        print(f"[dryrun] {cell_id}: ignoring unreadable "
                              f"cache record ({type(e).__name__}: {e})",
                              file=sys.stderr)
                rec = run_cell(arch, shape, mk, variant, args.out)
                if rec.get("ok") in (True, "skipped"):
                    n_ok += 1
                else:
                    n_fail += 1
                import jax

                jax.clear_caches()  # keep long sweeps from accumulating
    if tracer is not None:
        obs_trace.stop()
        tracer.write(args.trace)
        print(f"[dryrun] trace written to {args.trace} "
              f"({len(tracer.events)} events)")
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
