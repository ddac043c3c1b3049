"""Record the small scoped trace that ``test_scopes.py`` reads.

    python benchmarks/chip/record_scoped_tiny.py [--out PATH]

Two programs of the repo at published widths and two layers, their Pallas
kernels on, traced after a warm-up: OLMo-1B's prefill (flash attention) and
two of its decode steps against the KV cache, then RWKV6-1.6B's prefill of
one WKV chunk (the WKV6 kernel).  The host loop runs under an active
``repro.obs.trace`` tracer, so its spans are on the profile's host plane too.
Needs a TPU: the kernels are compiled for it.
"""
from __future__ import annotations

import argparse
import functools
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402

LAYERS = 2


def _config(name: str):
    conf = dict(harness.load_json(HERE / "configs" / f"{name}.json"), num_hidden_layers=LAYERS)
    return harness.program_config(conf)


# op metadata the reader does not read: the recording machine's file paths,
# and what repeats an op's HLO text
DROPPED = ("source", "source_stack", "shape_with_layout", "memory_access_breakdown")


def chips_only(space):
    """The chips' planes of ``space``, without the stats in ``DROPPED``: the
    host's planes and those stats would take the file past the size a test
    file may have, and the paths belong to the machine, not the program."""
    out = type(space)()
    out.planes.extend(p for p in space.planes if p.name.startswith("/device:TPU:"))
    for plane in out.planes:
        drop = {k for k, v in plane.stat_metadata.items() if v.name in DROPPED}
        for md in plane.event_metadata.values():
            kept = [st for st in md.stats if st.metadata_id not in drop]
            del md.stats[:]
            md.stats.extend(kept)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(HERE / "testdata" / "scoped_tiny.xplane.pb"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import use_pallas
    from repro.models import decode as dec
    from repro.models import init_params
    from repro.obs import trace

    olmo, rwkv = _config("olmo-1b"), _config("rwkv6-1.6b")
    B, P = 8, 128
    with use_pallas(True):  # read while the programs are traced, here
        o_params = jax.jit(functools.partial(init_params, olmo))(jax.random.PRNGKey(0))
        prompts = jnp.asarray(np.random.default_rng(0).integers(2, olmo.vocab_size, (B, P)),
                              jnp.int32)
        prefill = jax.jit(functools.partial(dec.prefill, olmo, capacity=P + 8))
        decode = jax.jit(functools.partial(dec.decode_step, olmo), donate_argnums=(1,))
        r_params = jax.jit(functools.partial(init_params, rwkv))(jax.random.PRNGKey(1))
        chunk = jnp.asarray(np.random.default_rng(1).integers(2, rwkv.vocab_size,
                                                              (2, rwkv.wkv_chunk)), jnp.int32)
        r_prefill = jax.jit(functools.partial(dec.prefill, rwkv))

        def run():
            with trace.span("serve.prefill"):
                logits, caches = prefill(o_params, prompts)
            for i in range(2):
                with trace.span("serve.decode_step"):
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
                    logits, caches = decode(o_params, caches, tok, jnp.int32(P + i))
            with trace.span("serve.prefill"):
                r_logits, _ = r_prefill(r_params, chunk)
            jax.block_until_ready((logits, r_logits))

        run()  # compile and warm every program
        tmp = HERE / ".runs" / "scoped_tiny"
        shutil.rmtree(tmp, ignore_errors=True)
        trace.start("scoped_tiny")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        with jax.profiler.trace(str(tmp), profiler_options=opts):
            run()
        trace.stop()
    import scopes

    space = scopes.read_xspace(str(tmp))
    spans = sum(1 for p in space.planes if p.name.startswith("/host:")
                for md in p.event_metadata.values() if md.name.startswith("serve."))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_bytes(chips_only(space).SerializeToString())
    print(f"wrote {args.out} ({Path(args.out).stat().st_size} bytes) on "
          f"{jax.devices()[0].device_kind}; {spans} span names of repro.obs.trace on the "
          f"host plane")
    scopes.load(args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
