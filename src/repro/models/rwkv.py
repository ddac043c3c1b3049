"""RWKV6 ("Finch") block: time-mix with data-dependent decay + channel-mix.

The WKV6 recurrence per head (key dim K, value dim V, both = rwkv_head_dim):

    y_t = r_t . (S_{t-1} + (u * k_t) v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T          w_t in (0,1), data-dependent

Three implementations, all agreeing (tested):
  * ``wkv_recurrent`` — step-by-step lax.scan (the oracle; also the decode
    step).
  * ``wkv_chunked``   — chunk-parallel form: intra-chunk pairwise decays via
    a (L, L, K) einsum, cross-chunk via a carried state.  This is the
    training path, and the algorithm mirrored by ``repro.kernels.rwkv6``.
  * Pallas TPU kernel (``repro.kernels.rwkv6``) for the hot path.

Stability: all decay algebra runs on log-decays; every exp() argument is a
*difference* of cumulative log-decays bounded above by 0, so nothing
overflows regardless of chunk length.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, dtype_of

WKV_CHUNK = 32
DECAY_LORA = 64


# --------------------------------------------------------------------------
# Parameters.
# --------------------------------------------------------------------------

def rwkv_params(cfg: ModelConfig, rng: jax.Array) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(rng, 12)
    H = d // cfg.rwkv_head_dim
    return {
        # time-mix
        "mu": 0.5 * jnp.ones((5, d), jnp.float32),  # w, r, k, v, g mixing
        "w0": jnp.full((d,), -1.0, jnp.float32),  # decay base (pre-softplus-ish)
        "decay_A": dense_init(ks[0], (d, DECAY_LORA), jnp.float32, fan_in=d),
        "decay_B": dense_init(ks[1], (DECAY_LORA, d), jnp.float32, fan_in=DECAY_LORA),
        "u": 0.1 * jnp.ones((d,), jnp.float32),  # per-channel bonus
        "wr": dense_init(ks[2], (d, d), dt),
        "wk": dense_init(ks[3], (d, d), dt),
        "wv": dense_init(ks[4], (d, d), dt),
        "wg": dense_init(ks[5], (d, d), dt),
        "wo": dense_init(ks[6], (d, d), dt),
        "ln_scale": jnp.ones((H, cfg.rwkv_head_dim), jnp.float32),  # group norm
        # channel-mix
        "cmu": 0.5 * jnp.ones((2, d), jnp.float32),  # k, r mixing
        "cm_k": dense_init(ks[7], (d, ff), dt),
        "cm_v": dense_init(ks[8], (ff, d), dt),
        "cm_r": dense_init(ks[9], (d, d), dt),
    }


# --------------------------------------------------------------------------
# WKV6 core.  r, k, v: (B, S, H, K); log_w: (B, S, H, K) (log decay, < 0);
# u: (H, K).  Returns y: (B, S, H, K) and final state (B, H, K, V).
# --------------------------------------------------------------------------

def wkv_recurrent(
    r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array, u: jax.Array,
    state0: jax.Array = None,
) -> Tuple[jax.Array, jax.Array]:
    B, S, H, K = r.shape
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    w = jnp.exp(log_w.astype(jnp.float32))
    s0 = state0 if state0 is not None else jnp.zeros((B, H, K, K), jnp.float32)

    def step(S_state, inp):
        rt, kt, vt, wt = inp  # each (B, H, K)
        kv = kt[..., :, None] * vt[..., None, :]  # (B,H,K,V)
        y = jnp.einsum("bhk,bhkv->bhv", rt, S_state + u[None, :, :, None] * kv)
        S_new = wt[..., None] * S_state + kv
        return S_new, y

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (rf, kf, vf, w))
    s_fin, ys = jax.lax.scan(step, s0, xs)
    return ys.transpose(1, 0, 2, 3).astype(r.dtype), s_fin


def wkv_decode_step(
    r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array, u: jax.Array,
    state: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One token: r,k,v,log_w (B, H, K); state (B, H, K, V)."""
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    w = jnp.exp(log_w.astype(jnp.float32))
    kv = kf[..., :, None] * vf[..., None, :]
    y = jnp.einsum("bhk,bhkv->bhv", rf, state + u[None, :, :, None] * kv)
    new_state = w[..., None] * state + kv
    return y.astype(r.dtype), new_state


def wkv_chunked(
    r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array, u: jax.Array,
    state0: jax.Array = None, chunk: int = WKV_CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    B, S, H, K = r.shape
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        zeros = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zeros(r), zeros(k), zeros(v)
        log_w = jnp.pad(log_w, ((0, 0), (0, pad), (0, 0), (0, 0)))  # pad w=e^0?? no:
        # padded positions must not pollute the carried state: give them
        # zero k/v (done by zeros()) and decay 1 (log 0) so state passes through.
        log_w = log_w.at[:, S:].set(0.0)
    n = r.shape[1] // L

    def to_chunks(a):
        return a.reshape(B, n, L, H, K).transpose(1, 0, 2, 3, 4).astype(jnp.float32)

    rc, kc, vc, lwc = map(to_chunks, (r, k, v, log_w))
    s0 = state0 if state0 is not None else jnp.zeros((B, H, K, K), jnp.float32)

    tri = jnp.tril(jnp.ones((L, L), bool), k=-1)  # strict lower: tau < t

    def chunk_step(S_state, inp):
        rr, kk, vv, lw = inp  # (B, L, H, K)
        cum = jnp.cumsum(lw, axis=1)  # inclusive cumulative log decay
        cum_ex = cum - lw  # exclusive: sum of log w over 1..t-1
        # intra-chunk: past contribution (s < t) carries decay
        # prod_{j=s+1}^{t-1} w_j = exp(cum_ex[t] - cum[s])   (w_t excluded,
        # matching S_{t-1} in the recurrence).
        D = cum_ex[:, :, None] - cum[:, None, :, :, :]  # (B,L,L,H,K)
        P = rr[:, :, None] * kk[:, None] * jnp.exp(jnp.minimum(D, 0.0))
        att = P.sum(-1) * tri[None, :, :, None]  # (B,L,L,H)
        y_intra = jnp.einsum("btsh,bshv->bthv", att, vv)
        # diagonal (current token) with bonus u
        y_diag = (rr * u[None, None] * kk).sum(-1, keepdims=True) * vv
        # cross-chunk: state entered the chunk before step 1; decay to t is
        # prod_{j=1}^{t-1} w_j = exp(cum_ex[t]).
        y_cross = jnp.einsum("bthk,bhkv->bthv", rr * jnp.exp(cum_ex), S_state)
        # state update: S' = exp(cum_L) * S + sum_s exp(cum_L - cum_s) k_s v_s
        A_L = jnp.exp(cum[:, -1])  # (B,H,K)
        decay_to_end = jnp.exp(cum[:, -1][:, None] - cum)  # (B,L,H,K) <= 1
        S_new = A_L[..., None] * S_state + jnp.einsum(
            "bthk,bthv->bhkv", kk * decay_to_end, vv
        )
        return S_new, y_intra + y_diag + y_cross

    s_fin, ys = jax.lax.scan(chunk_step, s0, (rc, kc, vc, lwc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, n * L, H, K)[:, :S]
    return y.astype(r.dtype), s_fin


# --------------------------------------------------------------------------
# Block application.
# --------------------------------------------------------------------------

def _shift(x: jax.Array, prev: jax.Array = None) -> jax.Array:
    """Token shift: x_prev[t] = x[t-1]; position 0 gets ``prev`` (or 0)."""
    first = prev[:, None] if prev is not None else jnp.zeros_like(x[:, :1])
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def _group_norm(x: jax.Array, scale: jax.Array, eps: float = 64e-5) -> jax.Array:
    """Per-head LayerNorm of (B, S, H, K)."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale[None, None]).astype(x.dtype)


def _time_mix_inputs(cfg: ModelConfig, p: dict, x: jax.Array, shifted: jax.Array):
    xf, sf = x.astype(jnp.float32), shifted.astype(jnp.float32)
    mixed = xf[None] + (sf - xf)[None] * p["mu"][:, None, None, :]  # (5,B,S,d)
    mw, mr, mk, mv, mg = mixed
    log_w = -jnp.exp(
        jnp.clip(p["w0"] + jnp.tanh(mw @ p["decay_A"]) @ p["decay_B"], -8.0, 8.0)
    )  # (B,S,d), < 0
    dt = x.dtype
    r = mr.astype(dt) @ p["wr"]
    k = mk.astype(dt) @ p["wk"]
    v = mv.astype(dt) @ p["wv"]
    g = jax.nn.silu(mg.astype(dt) @ p["wg"])
    return r, k, v, g, log_w


def _heads(cfg: ModelConfig, a: jax.Array) -> jax.Array:
    B, S, d = a.shape
    K = cfg.rwkv_head_dim
    return a.reshape(B, S, d // K, K)


def _wkv_dispatch(rh, kh, vh, lwh, u, chunked: bool, chunk: int = WKV_CHUNK):
    """Pallas kernel inside ``repro.kernels.use_pallas(True)``, else the
    pure-XLA chunked scan (the dry-run path) or the recurrence oracle."""
    from repro.kernels import config as kernels
    from repro.kernels.rwkv6 import ops as wkv_ops

    S = rh.shape[1]
    if kernels.pallas_enabled():
        if wkv_ops.supported(S, chunk):
            return wkv_ops.wkv(rh, kh, vh, lwh, u, chunk=chunk)
        kernels.fallback("wkv6", f"S={S} with chunk {chunk}")
    if chunked:
        return wkv_chunked(rh, kh, vh, lwh, u, chunk=chunk)
    return wkv_recurrent(rh, kh, vh, lwh, u)


def rwkv_time_mix(
    cfg: ModelConfig, p: dict, x: jax.Array, *, chunked: bool = True
) -> jax.Array:
    shifted = _shift(x)
    r, k, v, g, log_w = _time_mix_inputs(cfg, p, x, shifted)
    H = cfg.d_model // cfg.rwkv_head_dim
    u = p["u"].reshape(H, cfg.rwkv_head_dim)
    rh, kh, vh, lwh = map(lambda a: _heads(cfg, a), (r, k, v, log_w))
    y, _ = _wkv_dispatch(rh, kh, vh, lwh, u, chunked, cfg.wkv_chunk)
    y = _group_norm(y, p["ln_scale"])
    y = y.reshape(x.shape) * g
    return y @ p["wo"]


def rwkv_time_mix_prefill(
    cfg: ModelConfig, p: dict, x: jax.Array, *, chunked: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """Like rwkv_time_mix but also returns the final WKV state (B,H,K,V)."""
    shifted = _shift(x)
    r, k, v, g, log_w = _time_mix_inputs(cfg, p, x, shifted)
    H = cfg.d_model // cfg.rwkv_head_dim
    u = p["u"].reshape(H, cfg.rwkv_head_dim)
    rh, kh, vh, lwh = map(lambda a: _heads(cfg, a), (r, k, v, log_w))
    y, state = _wkv_dispatch(rh, kh, vh, lwh, u, chunked, cfg.wkv_chunk)
    y = _group_norm(y, p["ln_scale"])
    y = y.reshape(x.shape) * g
    return y @ p["wo"], state


def rwkv_channel_mix(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    shifted = _shift(x)
    xf, sf = x.astype(jnp.float32), shifted.astype(jnp.float32)
    mk = (xf + (sf - xf) * p["cmu"][0]).astype(x.dtype)
    mr = (xf + (sf - xf) * p["cmu"][1]).astype(x.dtype)
    kk = jnp.square(jax.nn.relu(mk @ p["cm_k"]))
    return jax.nn.sigmoid(mr @ p["cm_r"]) * (kk @ p["cm_v"])


# --------------------------------------------------------------------------
# Decode (single token) with carried state.
# cache = {"state": (B,H,K,V) f32, "tm_shift": (B,d), "cm_shift": (B,d)}
# --------------------------------------------------------------------------

def init_rwkv_cache(cfg: ModelConfig, batch: int) -> dict:
    d = cfg.d_model
    K = cfg.rwkv_head_dim
    H = d // K
    return {
        "state": jnp.zeros((batch, H, K, K), jnp.float32),
        "tm_shift": jnp.zeros((batch, d), dtype_of(cfg)),
        "cm_shift": jnp.zeros((batch, d), dtype_of(cfg)),
    }


def rwkv_time_mix_decode(
    cfg: ModelConfig, p: dict, x: jax.Array, cache: dict
) -> Tuple[jax.Array, dict]:
    B = x.shape[0]
    shifted = cache["tm_shift"][:, None]
    r, k, v, g, log_w = _time_mix_inputs(cfg, p, x, shifted)
    H = cfg.d_model // cfg.rwkv_head_dim
    u = p["u"].reshape(H, cfg.rwkv_head_dim)
    sq = lambda a: _heads(cfg, a)[:, 0]  # (B,H,K)
    y, new_state = wkv_decode_step(sq(r), sq(k), sq(v), sq(log_w), u, cache["state"])
    y = _group_norm(y[:, None].reshape(B, 1, H, cfg.rwkv_head_dim), p["ln_scale"])
    y = y.reshape(B, 1, cfg.d_model) * g
    out = y @ p["wo"]
    new_cache = dict(cache, state=new_state, tm_shift=x[:, 0])
    return out, new_cache


def rwkv_channel_mix_decode(
    cfg: ModelConfig, p: dict, x: jax.Array, cache: dict
) -> Tuple[jax.Array, dict]:
    shifted = cache["cm_shift"][:, None]
    xf, sf = x.astype(jnp.float32), shifted.astype(jnp.float32)
    mk = (xf + (sf - xf) * p["cmu"][0]).astype(x.dtype)
    mr = (xf + (sf - xf) * p["cmu"][1]).astype(x.dtype)
    kk = jnp.square(jax.nn.relu(mk @ p["cm_k"]))
    out = jax.nn.sigmoid(mr @ p["cm_r"]) * (kk @ p["cm_v"])
    return out, dict(cache, cm_shift=x[:, 0])
