"""Attention layers: GQA self-attention (global / sliding-window), cross-
attention, decode-with-cache.  Pure-JAX einsum formulation; heads stay in an
explicit (groups, heads-per-group) layout so GQA never materializes repeated
KV, and GSPMD shards the head dims over the "model" axis from the weight
shardings alone.

Full-sequence attention auto-switches to a KV-chunked online-softmax scan
(`chunked_attention`) above ``CHUNK_THRESHOLD`` keys, bounding activation
memory at O(S * chunk) instead of O(S^2) — this is also the reference
algorithm mirrored by ``repro.kernels.flash_attention``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import apply_rope, dense_init, dtype_of, softcap

CHUNK_THRESHOLD = 2048  # switch to chunked attention above this many keys
KV_CHUNK = 512

NEG_INF = -2.3819763e38  # large negative for masking (fits f32)


# --------------------------------------------------------------------------
# Parameters.
# --------------------------------------------------------------------------

def attn_params(cfg: ModelConfig, rng: jax.Array, kv_input_dim: Optional[int] = None) -> dict:
    """QKV + output projection.  ``kv_input_dim`` overrides the K/V input
    width for cross-attention over frontend embeddings (llama-vision)."""
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    kd = kv_input_dim or d
    dt = dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "wq": dense_init(k1, (d, H, dh), dt, fan_in=d),
        "wk": dense_init(k2, (kd, KV, dh), dt, fan_in=kd),
        "wv": dense_init(k3, (kd, KV, dh), dt, fan_in=kd),
        "wo": dense_init(k4, (H, dh, d), dt, fan_in=H * dh),
    }


def _split_groups(cfg: ModelConfig, q: jax.Array) -> jax.Array:
    """(B, S, H, dh) -> (B, S, G, M, dh) with G = kv heads, M = H // G."""
    B, S, H, dh = q.shape
    G = cfg.n_kv_heads
    return q.reshape(B, S, G, H // G, dh)


def _scale(cfg: ModelConfig) -> float:
    return cfg.head_dim_ ** -0.5


# --------------------------------------------------------------------------
# Mask helpers.  Positions are absolute token indices; window==0 -> global.
# ``causal=False`` is the encoder (bidirectional) case.
# --------------------------------------------------------------------------

def _mask_bias(
    q_pos: jax.Array,  # (Sq,)
    k_pos: jax.Array,  # (Sk,)
    window: int,
    causal: bool,
) -> jax.Array:
    """(Sq, Sk) additive f32 bias: 0 where attendable, NEG_INF elsewhere."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    ok &= k_pos[None, :] >= 0  # invalid / unwritten cache slots carry pos -1
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# --------------------------------------------------------------------------
# Core attention on explicit K/V (both dense and chunked paths).
# q: (B, Sq, G, M, dh); k, v: (B, Sk, G, dh).
# --------------------------------------------------------------------------

def _attend_dense(
    cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array, bias: jax.Array
) -> jax.Array:
    logits = jnp.einsum(
        "bsgmd,btgd->bgmst", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * _scale(cfg)
    logits = softcap(logits, cfg.attn_softcap)
    logits = logits + bias[None, None, None]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgmst,btgd->bsgmd", probs.astype(v.dtype), v)
    return out


def _attend_chunked(
    cfg: ModelConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    window: int,
    causal: bool,
) -> jax.Array:
    """Online-softmax over KV chunks (flash-attention recurrence, pure JAX)."""
    B, Sq, G, M, dh = q.shape
    Sk = k.shape[1]
    n_chunks = -(-Sk // KV_CHUNK)
    pad = n_chunks * KV_CHUNK - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-1)
    kc = k.reshape(B, n_chunks, KV_CHUNK, G, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, KV_CHUNK, G, dh).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, KV_CHUNK)

    qf = q.astype(jnp.float32) * _scale(cfg)

    def step(carry, chunk):
        m, l, acc = carry
        kj, vj, pj = chunk
        logits = jnp.einsum("bsgmd,btgd->bgmst", qf, kj.astype(jnp.float32))
        logits = softcap(logits, cfg.attn_softcap)
        logits = logits + _mask_bias(q_pos, pj, window, causal)[None, None, None]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        # guard fully-masked rows: keep m finite so exp() is well-defined
        m_safe = jnp.maximum(m_new, -1e30)
        p = jnp.exp(logits - m_safe[..., None])
        scale_old = jnp.exp(jnp.maximum(m, -1e30) - m_safe)
        l_new = l * scale_old + p.sum(axis=-1)
        acc_new = acc * scale_old[..., None] + jnp.einsum(
            "bgmst,btgd->bgmsd", p, vj.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, G, M, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, G, M, Sq), jnp.float32)
    a0 = jnp.zeros((B, G, M, Sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).astype(v.dtype)  # (B, Sq, G, M, dh)


# --------------------------------------------------------------------------
# Public layer entry points.
# --------------------------------------------------------------------------

def qkv_proj(
    cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Project + rope.  Returns q (B,S,H,dh), k, v (B,S,G,dh)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dgk->bsgk", x, p["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", x, p["wv"])
    if cfg.pos == "rope":
        q = apply_rope(q, positions[None], cfg.rope_theta)
        k = apply_rope(k, positions[None], cfg.rope_theta)
    return q, k, v


def attend(
    cfg: ModelConfig,
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Sk, G, dh)
    v: jax.Array,
    q_pos: jax.Array,  # (Sq,)
    k_pos: jax.Array,  # (Sk,)
    *,
    window: int = 0,
    causal: bool = True,
) -> jax.Array:
    """Masked attention core; auto-chunks above CHUNK_THRESHOLD keys.
    Returns (B, Sq, H, dh).  Inside ``repro.kernels.use_pallas(True)`` and
    with contiguous positions, dispatches to the Pallas flash kernel."""
    from repro.kernels import config as kernels

    B, Sq = q.shape[:2]
    # Sq != Sk is decode against a cache: the kernel is for full sequences
    if kernels.pallas_enabled() and Sq == k.shape[1]:
        from repro.kernels.flash_attention import ops as fa_ops

        if fa_ops.supported(Sq, k.shape[1], cfg.head_dim_):
            return fa_ops.attention(
                q, k, v, causal=causal, window=window,
                softcap=cfg.attn_softcap,
            )
        kernels.fallback("flash_attention", f"S={Sq}, dh={cfg.head_dim_}")
    qg = _split_groups(cfg, q)
    if k.shape[1] > CHUNK_THRESHOLD:
        out = _attend_chunked(cfg, qg, k, v, q_pos, k_pos, window, causal)
    else:
        bias = _mask_bias(q_pos, k_pos, window, causal)
        out = _attend_dense(cfg, qg, k, v, bias)
    return out.reshape(B, Sq, cfg.n_heads, cfg.head_dim_)


def out_proj(p: dict, o: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def self_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,  # (S,)
    *,
    window: int = 0,
    causal: bool = True,
) -> jax.Array:
    """Full-sequence self-attention (train / prefill / encoder)."""
    q, k, v = qkv_proj(cfg, p, x, positions)
    out = attend(cfg, q, k, v, positions, positions, window=window, causal=causal)
    return out_proj(p, out)


def cross_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, S, d)
    kv: Tuple[jax.Array, jax.Array],  # precomputed (B, T, G, dh) pairs
) -> jax.Array:
    """Cross-attention over precomputed K/V (encoder output / image patches).
    No positional rotation, no mask (all frontend tokens visible)."""
    B, S, _ = x.shape
    k, v = kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    qg = _split_groups(cfg, q)
    T = k.shape[1]
    zeros_q = jnp.zeros((S,), jnp.int32)
    zeros_k = jnp.zeros((T,), jnp.int32)
    if T > CHUNK_THRESHOLD:
        out = _attend_chunked(cfg, qg, k, v, zeros_q, zeros_k, 0, causal=False)
    else:
        bias = jnp.zeros((S, T), jnp.float32)
        out = _attend_dense(cfg, qg, k, v, bias)
    out = out.reshape(B, S, cfg.n_heads, cfg.head_dim_)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_kv(cfg: ModelConfig, p: dict, enc: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from encoder / frontend states."""
    k = jnp.einsum("btf,fgk->btgk", enc, p["wk"])
    v = jnp.einsum("btf,fgk->btgk", enc, p["wv"])
    return k, v


# --------------------------------------------------------------------------
# KV cache (decode).  Two layouts:
#   * global layers: capacity S_max, write at absolute position.
#   * local (sliding-window) layers: ring buffer of size ``window``.
# ``pos`` entries are absolute key positions (-1 = unwritten, masked out).
# --------------------------------------------------------------------------

def init_kv_cache(
    cfg: ModelConfig, batch: int, capacity: int, dtype=None
) -> dict:
    G, dh = cfg.n_kv_heads, cfg.head_dim_
    dt = dtype or dtype_of(cfg)
    return {
        "k": jnp.zeros((batch, capacity, G, dh), dt),
        "v": jnp.zeros((batch, capacity, G, dh), dt),
        "pos": jnp.full((capacity,), -1, jnp.int32),
    }


def cache_capacity(window: int, seq_len: int) -> int:
    return min(window, seq_len) if window else seq_len


def cache_from_kv(
    k: jax.Array,  # (B, S, G, dh) — rope already applied
    v: jax.Array,
    positions: jax.Array,  # (S,)
    capacity: int,
) -> dict:
    """Build a decode cache from prefill K/V (keeps the trailing ``capacity``
    positions in ring-buffer layout for local layers)."""
    S = k.shape[1]
    if capacity >= S:
        padk = jnp.pad(k, ((0, 0), (0, capacity - S), (0, 0), (0, 0)))
        padv = jnp.pad(v, ((0, 0), (0, capacity - S), (0, 0), (0, 0)))
        pos = jnp.pad(positions, (0, capacity - S), constant_values=-1)
        return {"k": padk, "v": padv, "pos": pos}
    # ring layout: slot = pos % capacity; the last `capacity` tokens survive.
    tail_k, tail_v = k[:, -capacity:], v[:, -capacity:]
    tail_pos = positions[-capacity:]
    slots = tail_pos % capacity
    order = jnp.argsort(slots)
    return {
        "k": tail_k[:, order],
        "v": tail_v[:, order],
        "pos": tail_pos[order],
    }


def decode_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, 1, d)
    pos: jax.Array,  # scalar int32 — absolute position of the new token
    cache: dict,
    *,
    window: int = 0,
) -> Tuple[jax.Array, dict]:
    """One-token self-attention against (and updating) the KV cache."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k_new = jnp.einsum("bsd,dgk->bsgk", x, p["wk"])
    v_new = jnp.einsum("bsd,dgk->bsgk", x, p["wv"])
    if cfg.pos == "rope":
        pos_b = jnp.broadcast_to(pos, (1, 1))
        q = apply_rope(q, pos_b, cfg.rope_theta)
        k_new = apply_rope(k_new, pos_b, cfg.rope_theta)

    capacity = cache["k"].shape[1]
    slot = jnp.where(window > 0, pos % capacity, jnp.minimum(pos, capacity - 1))
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
    kpos = jax.lax.dynamic_update_slice(cache["pos"], pos[None].astype(jnp.int32), (slot,))
    new_cache = {"k": k, "v": v, "pos": kpos}

    qg = _split_groups(cfg, q)  # (B, 1, G, M, dh)
    bias = _mask_bias(pos[None].astype(jnp.int32), kpos, window, causal=True)
    out = _attend_dense(cfg, qg, k, v, bias)
    out = out.reshape(B, 1, cfg.n_heads, cfg.head_dim_)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache
