"""The names the program gives its work on the device, and its host spans.

The model's programs name their parts with ``jax.named_scope`` (root scopes
``prefill``, ``decode``, ``train_step``; ``layers`` around each layer scan;
``attn`` with ``kv_cache``, ``mlp``, ``time_mix`` with ``wkv``,
``channel_mix``; ``embed``, ``lm_head``; ``loss`` and ``optimizer``), and the
compiler keeps each in its ops' ``op_name`` — the name the device profiler
shows.  ``obs.trace.span`` writes its spans into a ``jax.profiler`` trace too.
"""
import functools
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.configs.base import RunConfig
from repro.models import decode as dec
from repro.models import init_params
from repro.models.steps import train_step
from repro.obs import trace
from repro.optim import adamw

B, S, CAP = 2, 64, 80

# (arch, program) -> op_name patterns the compiled program must carry
EXPECTED = {
    ("olmo-1b", "prefill"): [
        r"/prefill/embed/", r"/prefill/layers/while/", r"/prefill/layers/.*/attn/",
        r"/prefill/layers/.*/attn/kv_cache/", r"/prefill/layers/.*/mlp/",
        r"/prefill/lm_head/"],
    ("olmo-1b", "decode"): [
        r"/decode/embed/", r"/decode/layers/attn/kv_cache/dynamic_update_slice",
        r"/decode/layers/.*/attn/kv_cache/", r"/decode/layers/.*/attn/[^k]",
        r"/decode/layers/.*/mlp/", r"/decode/lm_head/",
        # the scan's stacking of the layers' new tokens, written after it
        r"/decode/layers/while/body/dynamic_update_slice"],
    ("olmo-1b", "train"): [
        r"/train_step/jvp\(loss\)/embed/", r"/train_step/jvp\(loss\)/layers/while/",
        r"/train_step/jvp\(loss\)/layers/.*/attn/", r"/train_step/jvp\(loss\)/layers/.*/mlp/",
        r"/train_step/jvp\(loss\)/lm_head/",
        r"/train_step/transpose\(jvp\(loss\)\)/layers/.*/attn/",
        r"/train_step/optimizer/"],
    ("rwkv6-1.6b", "prefill"): [
        r"/prefill/embed/", r"/prefill/layers/.*/time_mix/wkv/",
        r"/prefill/layers/.*/time_mix/[^w]", r"/prefill/layers/.*/channel_mix/",
        r"/prefill/lm_head/"],
    ("rwkv6-1.6b", "decode"): [
        r"/decode/layers/.*/time_mix/wkv/", r"/decode/layers/.*/channel_mix/",
        r"/decode/lm_head/"],
    ("rwkv6-1.6b", "train"): [
        r"/train_step/jvp\(loss\)/layers/.*/time_mix/wkv/",
        r"/train_step/transpose\(jvp\(loss\)\)/layers/.*/channel_mix/",
        r"/train_step/optimizer/"],
}


def _op_names(arch: str, program: str):
    cfg = smoke_config(arch)
    params = jax.eval_shape(functools.partial(init_params, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if program == "prefill":
        lowered = jax.jit(functools.partial(dec.prefill, cfg, capacity=CAP)).lower(params, tokens)
    elif program == "decode":
        caches = jax.eval_shape(lambda p, t: dec.prefill(cfg, p, t, capacity=CAP)[1],
                                params, tokens)
        lowered = jax.jit(functools.partial(dec.decode_step, cfg)).lower(
            params, caches, jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    else:
        run = RunConfig(model=cfg, seq_len=S, global_batch=B)
        lowered = jax.jit(functools.partial(train_step, cfg, run)).lower(
            params, jax.eval_shape(adamw.init_state, params), {"tokens": tokens})
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


@pytest.mark.parametrize("arch,program", sorted(EXPECTED))
def test_programs_carry_their_scopes(arch, program):
    names = _op_names(arch, program)
    root = {"train": "train_step"}.get(program, program)
    # every op of the program's own computations (not of a reduction's body,
    # which names its ops relative to the reduction) is under the root; in
    # training, jax.checkpoint hoists a layer's loop-invariant work (masks)
    # out of the scan with a name relative to the layer
    top = [n for n in names if n.startswith("jit(")]
    stray = [n for n in top if f"/{root}/" not in n
             and not (program == "train" and re.match(r"jit\([^/]*\)/(attn|time_mix)/", n))]
    assert top and not stray, stray[:5]
    for pattern in EXPECTED[arch, program]:
        assert any(re.search(pattern, n) for n in names), (pattern, sorted(set(names))[:40])
    if program == "train":  # the backward pass is marked, and only it
        assert any("transpose(" in n for n in names)
        assert not any("transpose(" in n for n in names if "/optimizer/" in n)


def _host_event_names(logdir: str):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))[-1]
    pd = ProfileData.from_file(path)
    return {e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    before = time.time_ns()
    tracer = trace.start("t")
    try:
        assert before <= tracer.metadata["t0_unix_ns"] <= time.time_ns()
        with jax.profiler.trace(str(tmp_path)):
            with trace.span("serve.decode_step", token=0):
                jnp.ones(8).sum().block_until_ready()
    finally:
        trace.stop()
    assert "serve.decode_step" in _host_event_names(str(tmp_path))
    assert [e["name"] for e in tracer.events if e["ph"] == "X"] == ["serve.decode_step"]


def test_span_off_without_a_tracer(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("serve.quiet"):
            jnp.ones(8).sum().block_until_ready()
    assert "serve.quiet" not in _host_event_names(str(tmp_path))


def test_serve_loop_spans_on_the_profiler_host_plane(tmp_path):
    from repro.launch.serve import main

    with jax.profiler.trace(str(tmp_path / "profile")):
        main(["--arch", "llama3.2-1b", "--smoke", "--batch", "2", "--prompt-len", "8",
              "--new-tokens", "3", "--trace", str(tmp_path / "serve.json")])
    names = _host_event_names(str(tmp_path / "profile"))
    assert {"serve.prefill", "serve.readback", "serve.plan", "serve.decode_step"} <= names
