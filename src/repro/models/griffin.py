"""Griffin / RecurrentGemma RG-LRU recurrent block.

Block wiring (Griffin, arXiv:2402.19427):

    gate  = GeLU(W_gate x)                      (d -> W)
    u     = causal_conv1d(W_in x, width=4)      (d -> W, depthwise conv)
    h     = RG-LRU(u)                           (W -> W, diagonal recurrence)
    out   = W_out (gate * h)                    (W -> d)

RG-LRU recurrence (c = 8):

    r_t = sigmoid(BlockDiag_a(u_t))             recurrence gate
    i_t = sigmoid(BlockDiag_x(u_t))             input gate
    a_t = exp(-c * softplus(Lambda) * r_t)      data-dependent diag decay
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * u_t)

The recurrence is a first-order diagonal linear system, so training uses
``jax.lax.associative_scan`` (O(log S) depth); decode is the single-step
form.  ``repro.kernels.rglru`` holds the Pallas TPU kernel for the scan.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, dtype_of

N_BLOCKS = 8
C_RGLRU = 8.0


def lru_width(cfg: ModelConfig) -> int:
    return cfg.lru_width or cfg.d_model


def rglru_params(cfg: ModelConfig, rng: jax.Array) -> dict:
    d, W = cfg.d_model, lru_width(cfg)
    dt = dtype_of(cfg)
    ks = jax.random.split(rng, 6)
    bw = W // N_BLOCKS
    return {
        "w_gate": dense_init(ks[0], (d, W), dt, fan_in=d),
        "w_in": dense_init(ks[1], (d, W), dt, fan_in=d),
        "conv_w": dense_init(ks[2], (cfg.conv_width, W), dt, fan_in=cfg.conv_width),
        "conv_b": jnp.zeros((W,), dt),
        "gate_a": dense_init(ks[3], (N_BLOCKS, bw, bw), jnp.float32, fan_in=bw),
        "gate_x": dense_init(ks[4], (N_BLOCKS, bw, bw), jnp.float32, fan_in=bw),
        # Lambda init so a^c spans ~(0.9, 0.999) as in the paper
        "lam": jnp.linspace(2.0, 6.0, W).astype(jnp.float32),
        "w_out": dense_init(ks[5], (W, d), dt, fan_in=W),
    }


def _block_linear(w: jax.Array, x: jax.Array) -> jax.Array:
    """Block-diagonal linear: x (..., W) @ blockdiag(w (N, bw, bw))."""
    shape = x.shape
    xb = x.reshape(shape[:-1] + (N_BLOCKS, shape[-1] // N_BLOCKS))
    yb = jnp.einsum("...nw,nwk->...nk", xb, w)
    return yb.reshape(shape)


def _gates(p: dict, u: jax.Array) -> Tuple[jax.Array, jax.Array]:
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(_block_linear(p["gate_a"], uf))
    i = jax.nn.sigmoid(_block_linear(p["gate_x"], uf))
    log_a = -C_RGLRU * jax.nn.softplus(p["lam"]) * r  # (<= 0)
    a = jnp.exp(log_a)
    # sqrt(1 - a^2) computed stably via expm1: 1 - exp(2 log_a)
    b_scale = jnp.sqrt(-jnp.expm1(2.0 * log_a))
    gated_in = b_scale * i * uf
    return a, gated_in


def _scan_dispatch(a: jax.Array, gin: jax.Array) -> jax.Array:
    """Pallas kernel inside ``repro.kernels.use_pallas(True)``, else XLA
    associative_scan."""
    from repro.kernels import pallas_enabled

    if pallas_enabled():
        from repro.kernels.rglru import ops as lru_ops

        return lru_ops.scan(a, gin)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, hh = jax.lax.associative_scan(combine, (a, gin), axis=1)
    return hh


def rglru_scan(p: dict, u: jax.Array) -> jax.Array:
    """Full-sequence RG-LRU.  u: (B, S, W) -> h: (B, S, W)."""
    a, gin = _gates(p, u)  # (B, S, W) f32
    return _scan_dispatch(a, gin).astype(u.dtype)


def rglru_step(p: dict, u: jax.Array, h: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One step.  u: (B, W); h: (B, W) f32 carried state."""
    a, gin = _gates(p, u)
    h_new = a * h + gin
    return h_new.astype(u.dtype), h_new


def causal_conv(p: dict, u: jax.Array) -> jax.Array:
    """Depthwise causal conv, width cfg.conv_width.  u: (B, S, W)."""
    width = p["conv_w"].shape[0]
    pad = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + u.shape[1]] * p["conv_w"][width - 1 - i][None, None]
        for i in range(width)
    )
    return out + p["conv_b"][None, None]


def causal_conv_step(p: dict, u: jax.Array, conv_state: jax.Array):
    """u: (B, W) new input; conv_state: (B, width-1, W) previous inputs."""
    width = p["conv_w"].shape[0]
    window = jnp.concatenate([conv_state, u[:, None]], axis=1)  # (B, width, W)
    # window is ordered oldest -> newest; conv_w[j] weights the input j steps
    # back, so the newest entry takes conv_w[0]: flip the taps.
    out = jnp.einsum("bwd,wd->bd", window, p["conv_w"][::-1]) + p["conv_b"][None]
    return out, window[:, 1:]


# --------------------------------------------------------------------------
# Full block.
# --------------------------------------------------------------------------

def rglru_block(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Full-sequence recurrent block.  x: (B, S, d)."""
    gate = jax.nn.gelu(x @ p["w_gate"], approximate=True)
    u = causal_conv(p, x @ p["w_in"])
    h = rglru_scan(p, u)
    return (gate * h) @ p["w_out"]


def rglru_block_prefill(
    cfg: ModelConfig, p: dict, x: jax.Array
) -> Tuple[jax.Array, dict]:
    """Full-sequence block that also returns the decode cache."""
    gate = jax.nn.gelu(x @ p["w_gate"], approximate=True)
    u_raw = x @ p["w_in"]
    u = causal_conv(p, u_raw)
    a, gin = _gates(p, u)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, hh = jax.lax.associative_scan(combine, (a, gin), axis=1)
    h = hh.astype(u.dtype)
    width = cfg.conv_width
    conv_tail = u_raw[:, -(width - 1):]
    S = u_raw.shape[1]
    if S < width - 1:  # pad front with zeros (cold conv state)
        conv_tail = jnp.pad(conv_tail, ((0, 0), (width - 1 - S, 0), (0, 0)))
    cache = {"h": hh[:, -1].astype(jnp.float32), "conv": conv_tail}
    return (gate * h) @ p["w_out"], cache


def init_rglru_cache(cfg: ModelConfig, batch: int) -> dict:
    W = lru_width(cfg)
    return {
        "h": jnp.zeros((batch, W), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, W), dtype_of(cfg)),
    }


def rglru_block_decode(
    cfg: ModelConfig, p: dict, x: jax.Array, cache: dict
) -> Tuple[jax.Array, dict]:
    """x: (B, 1, d) -> (y, new_cache)."""
    xt = x[:, 0]
    gate = jax.nn.gelu(xt @ p["w_gate"], approximate=True)
    u_raw = xt @ p["w_in"]
    u, conv_state = causal_conv_step(p, u_raw, cache["conv"])
    h_out, h_state = rglru_step(p, u, cache["h"])
    y = ((gate * h_out) @ p["w_out"])[:, None]
    return y, {"h": h_state, "conv": conv_state}
