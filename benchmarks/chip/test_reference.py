"""The plain references against the program's jnp path, both in float32, on
the CPU at a small size: the full forward, prefill then decode through the
cache, the loss, and three optimizer steps."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import refmath
import smoke

# every configuration of BENCHMARK.json that names a reference, by that name
CONFIGS = [pytest.param(conf, id=conf["reference"]) for conf in (
    harness.load_json(harness.CHECKOUT / c["file"])
    for c in harness.load_json(harness.CHECKOUT / "BENCHMARK.json")["configs"])
    if "reference" in conf]


def small(conf):
    """``conf`` at its reference's ``SMALL`` widths, the program's
    ModelConfig of it in float32, and the reference's float32 weights."""
    conf = smoke.small_config(conf)
    cfg = dataclasses.replace(harness.program_config(conf), dtype="float32")
    params = harness.load_module("reference", conf["reference"]).weights(
        conf, refmath.seed_key(2 ** 31 + 3), dtype=jnp.float32)
    return conf, cfg, params


def tokens(conf, n, T, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(2, conf["vocab_size"], (n, T)),
                       jnp.int32)


@pytest.mark.parametrize("conf", CONFIGS)
def test_weights_have_the_program_layout(conf):
    from repro.models import init_params

    conf, cfg, params = small(conf)
    want = jax.eval_shape(functools.partial(init_params, cfg), jax.random.PRNGKey(0))
    assert harness.layout(params) == harness.layout(want)


@pytest.mark.parametrize("conf", CONFIGS)
def test_forward(conf):
    from repro.models.transformer import forward

    conf, cfg, params = small(conf)
    ref = harness.load_module("reference", conf["reference"])
    toks = tokens(conf, 2, 40)
    got = forward(cfg, params, toks)[0][..., : conf["vocab_size"]]
    want = ref.logits(conf, params, toks, 0)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("conf", CONFIGS)
def test_prefill_then_decode(conf):
    from repro.models import decode as dec

    conf, cfg, params = small(conf)
    ref = harness.load_module("reference", conf["reference"])
    P, N = 32, 6
    toks = tokens(conf, 2, P + N)
    want = ref.logits(conf, params, toks, P - 1)  # (2, N + 1, V)
    logits, caches = dec.prefill(cfg, params, toks[:, :P], capacity=P + N)
    got = [logits]
    for i in range(N):
        logits, caches = dec.decode_step(cfg, params, caches, toks[:, P + i: P + i + 1],
                                         jnp.int32(P + i))
        got.append(logits)
    got = jnp.stack(got, 1)[..., : conf["vocab_size"]]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4 * float(jnp.abs(want).max()))


def test_three_optimizer_steps():
    from repro.configs.base import RunConfig
    from repro.models.steps import train_step
    from repro.optim import adamw as program_adamw

    conf, cfg, params = small(harness.cell("olmo-1b.train.s2048").config)
    ref = harness.load_module("reference", conf["reference"])
    adamw = harness.load_module("reference", "adamw")
    hp = harness.load_json(harness.HERE / "traffic" / "train.s2048.json")
    hp = dict(hp, warmup_steps=2, total_steps=10, grad_clip=0.5)  # both schedule branches, clipping
    run = RunConfig(model=cfg, seq_len=24, global_batch=2, n_microbatches=1, remat=False,
                    learning_rate=hp["lr"], warmup_steps=hp["warmup_steps"],
                    total_steps=hp["total_steps"], weight_decay=hp["weight_decay"],
                    grad_clip=hp["grad_clip"])
    batches = [tokens(conf, 2, 24, seed=s) for s in range(3)]
    p, opt = params, program_adamw.init_state(params)
    losses = []
    for b in batches:
        p, opt, m = train_step(cfg, run, p, opt, {"tokens": b})
        losses.append(float(m["loss"]))
    want_losses, first, want = adamw.train(functools.partial(ref.loss, conf), params, batches, hp)
    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    assert first.shape == (len(refmath.slice_names(params)),)


def test_fp8_control_rounds_harder_than_bf16():
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    e8 = float(jnp.abs(refmath.fp8(x) - x).max() / jnp.abs(x).max())
    e16 = float(jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).max() / jnp.abs(x).max())
    assert e8 > 4 * e16


@pytest.mark.parametrize("fmt,dtype", [(refmath.E4M3FN, jnp.float8_e4m3fn),
                                       (refmath.E5M2, jnp.float8_e5m2)])
def test_quantize_is_the_narrow_type(fmt, dtype):
    """Equal, value for value, to a cast to the narrow type, subnormals and
    the largest finite included; the same under jit, where a compiler may
    drop a round trip of converts but not this arithmetic."""
    bits, min_exp, largest = fmt
    key = jax.random.PRNGKey(3)
    mags = jnp.exp2(jax.random.uniform(key, (4096,), minval=min_exp - bits - 2,
                                       maxval=np.log2(largest)))
    x = jnp.concatenate([mags * jnp.sign(jax.random.normal(key, (4096,))),
                         jnp.array([0.0, largest, -largest, 2.0 ** min_exp])])
    want = np.asarray(x.astype(dtype).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(refmath.quantize(x, fmt)), want)
    np.testing.assert_array_equal(np.asarray(jax.jit(refmath.quantize, static_argnums=1)(x, fmt)),
                                  want)
