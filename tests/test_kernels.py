"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru.kernel import rglru_scan
from repro.kernels.rglru.ref import rglru_ref
from repro.kernels.rwkv6.kernel import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref

RNG = np.random.default_rng(0)


def _randn(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# -- flash attention -----------------------------------------------------------

FA_CASES = [
    # (B, H, G, S, dh, dtype, kwargs)
    (1, 2, 2, 128, 64, jnp.float32, {}),
    (2, 4, 2, 256, 64, jnp.float32, {"window": 64}),
    (1, 8, 1, 128, 128, jnp.float32, {}),  # MQA
    (2, 2, 2, 192, 64, jnp.float32, {"causal": False}),
    (1, 2, 2, 256, 64, jnp.bfloat16, {}),
    (1, 2, 2, 128, 64, jnp.float32, {"softcap": 20.0}),
    (1, 2, 2, 128, 64, jnp.float32, {"window": 32, "softcap": 10.0}),
]


@pytest.mark.parametrize("B,H,G,S,dh,dtype,kw", FA_CASES)
def test_flash_attention_vs_ref(B, H, G, S, dh, dtype, kw):
    q = _randn((B, H, S, dh), dtype)
    k = _randn((B, G, S, dh), dtype)
    v = _randn((B, G, S, dh), dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True, **kw)
    ref = attention_ref(q, k, v, **kw)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_block_shape_invariance():
    q = _randn((1, 2, 256, 64), jnp.float32)
    k = _randn((1, 2, 256, 64), jnp.float32)
    v = _randn((1, 2, 256, 64), jnp.float32)
    o1 = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    o2 = flash_attention(q, k, v, block_q=128, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_flash_attention_q_offset_decode_tail():
    """Query block taken from the middle of the sequence (chunked prefill)."""
    S, tail = 256, 64
    q = _randn((1, 2, S, 64), jnp.float32)
    k = _randn((1, 2, S, 64), jnp.float32)
    v = _randn((1, 2, S, 64), jnp.float32)
    full = attention_ref(q, k, v, causal=True)
    part = flash_attention(
        q[:, :, -tail:], k, v, q_offset=S - tail, block_q=32, block_k=64,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(part), np.asarray(full[:, :, -tail:]), atol=2e-5
    )


# -- wkv6 -----------------------------------------------------------------------

WKV_CASES = [
    (1, 64, 2, 64, 16),
    (2, 128, 3, 64, 32),
    (1, 96, 1, 32, 32),  # S % chunk != 0 upstream guard -> chunk 32 divides 96
]


@pytest.mark.parametrize("B,S,H,K,chunk", WKV_CASES)
def test_wkv6_vs_ref(B, S, H, K, chunk):
    r = _randn((B, S, H, K), jnp.float32)
    k = _randn((B, S, H, K), jnp.float32) * 0.5
    v = _randn((B, S, H, K), jnp.float32)
    log_w = -jnp.exp(_randn((B, S, H, K), jnp.float32))
    u = _randn((H, K), jnp.float32) * 0.1
    y, fin = wkv6(r, k, v, log_w, u, chunk=chunk, interpret=True)
    yr, finr = wkv6_ref(r, k, v, log_w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr), atol=2e-4, rtol=2e-4)


def test_wkv6_strong_decay_stable():
    """Very strong decay (w -> 0) must not overflow the chunked algebra."""
    B, S, H, K = 1, 64, 1, 32
    r = _randn((B, S, H, K), jnp.float32)
    k = _randn((B, S, H, K), jnp.float32)
    v = _randn((B, S, H, K), jnp.float32)
    log_w = jnp.full((B, S, H, K), -50.0)  # w = e^-50
    u = jnp.zeros((H, K))
    y, fin = wkv6(r, k, v, log_w, u, chunk=16, interpret=True)
    yr, _ = wkv6_ref(r, k, v, log_w, u)
    assert bool(jnp.isfinite(y).all())
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)


def test_wkv_chunked_model_path_matches_recurrent():
    """The model's pure-XLA chunked WKV == the recurrence (models/rwkv)."""
    from repro.models.rwkv import wkv_chunked, wkv_recurrent

    B, S, H, K = 2, 70, 2, 16  # deliberately not a chunk multiple
    r = _randn((B, S, H, K), jnp.float32)
    k = _randn((B, S, H, K), jnp.float32)
    v = _randn((B, S, H, K), jnp.float32)
    log_w = -jnp.exp(_randn((B, S, H, K), jnp.float32))
    u = _randn((H, K), jnp.float32) * 0.1
    y1, s1 = wkv_chunked(r, k, v, log_w, u, chunk=32)
    y2, s2 = wkv_recurrent(r, k, v, log_w, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-4, rtol=2e-4)


# -- rglru ------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,W,chunk,bw", [
    (1, 128, 128, 64, 128),
    (2, 256, 256, 128, 128),
    (1, 64, 512, 32, 256),
])
def test_rglru_vs_ref(B, S, W, chunk, bw):
    a = jnp.asarray(RNG.uniform(0.3, 0.999, (B, S, W)), jnp.float32)
    b = _randn((B, S, W), jnp.float32)
    y = rglru_scan(a, b, chunk=chunk, block_w=bw, interpret=True)
    yr, _ = rglru_ref(a, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5, rtol=1e-4)


def test_rglru_bf16():
    a = jnp.asarray(RNG.uniform(0.5, 0.99, (1, 128, 128)), jnp.bfloat16)
    b = _randn((1, 128, 128), jnp.bfloat16)
    y = rglru_scan(a, b, chunk=64, interpret=True)
    yr, _ = rglru_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=0.15, rtol=0.1
    )


# -- dispatch -------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["flash_attention", "rglru", "wkv6"])
def test_enabled_kernel_falls_back_visibly(kernel, caplog):
    """A shape the kernel cannot take runs the reference, with a log line
    and a kernels.fallback counter."""
    from repro.kernels import use_pallas
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.rglru import ops as lru_ops
    from repro.kernels.rwkv6 import ops as wkv_ops
    from repro.obs import metrics

    metrics.enable()
    if kernel == "flash_attention":
        q = _randn((1, 192, 2, 32), jnp.float32)  # 192 is no multiple of 128
        call = lambda: fa_ops.attention(q, q, q)
        want = attention_ref(*(a.transpose(0, 2, 1, 3) for a in (q, q, q))).transpose(0, 2, 1, 3)
    elif kernel == "rglru":
        a = jnp.asarray(RNG.uniform(0.5, 0.99, (1, 192, 128)), jnp.float32)
        call = lambda: lru_ops.scan(a, a)
        want = rglru_ref(a, a)[0]
    else:
        r = _randn((1, 40, 1, 16), jnp.float32)  # 40 is no multiple of 32
        call = lambda: wkv_ops.wkv(r, r, r, -jnp.abs(r), jnp.zeros((1, 16)))[0]
        want = wkv6_ref(r, r, r, -jnp.abs(r), jnp.zeros((1, 16)))[0]
    with use_pallas(True), caplog.at_level("WARNING", logger="repro.kernels.config"):
        got = call()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert metrics.registry().counters[f"kernels.fallback.{kernel}"].value == 1
    assert any(kernel in r.getMessage() for r in caplog.records)
