"""Plain float32 reference of OLMo (Groeneveld et al., arXiv:2402.00838), and the
weights the benchmark draws from the seed.

The model, as the paper's section 2.1 describes OLMo-1B: a token embedding
that the output head shares; ``num_hidden_layers`` pre-norm blocks, each
causal multi-head self-attention with rotary position embeddings (rotate-half
form, base ``rope_theta``) and a SwiGLU MLP; a non-parametric LayerNorm (no
scale, no bias) before each half of a block and before the head; no biases
anywhere.  Everything is float32 with matmuls at ``Precision.HIGHEST``.

Departures, each because the system under test does the same and the
comparison is of the system, not of the paper's checkpoint:

- weights are random (``weights``), not the released ones;
- the LayerNorm epsilon is 1e-5 (the paper does not state it);
- the SwiGLU input matrix is one ``(d, 2 * d_ff)`` array, gate half first;
- the embedding table has the program's row count (``vocab_size`` rounded up
  to a multiple of 256); rows past ``vocab_size`` are zero and the head reads
  only the first ``vocab_size`` rows.

The reference is computed layer by layer in a scan, so its activations are
one layer's; the serving check also reads the logits of chosen positions only.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from refmath import layernorm, mm, normal

LN_EPS = 1e-5
# widths of the CPU tests (smoke.shrink, test_reference.py)
SMALL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             head_dim=16, intermediate_size=128, vocab_size=256)


def dims(conf: dict):
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    G = conf["num_key_value_heads"]
    dh = conf.get("head_dim") or d // H
    return d, H, G, dh, conf["intermediate_size"], conf["vocab_size"], conf["num_hidden_layers"]


def table_rows(conf: dict) -> int:
    return math.ceil(conf["vocab_size"] / 256) * 256


def weights(conf: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in the layout the program takes (traceable; jit it)."""
    d, H, G, dh, ff, V, L = dims(conf)
    ks = jax.random.split(key, 7)
    tok = normal(ks[0], (table_rows(conf), d), d, dtype)
    tok = tok.at[V:].set(0)
    layer = {
        "ln1": None,
        "ln2": None,
        "attn": {
            "wq": normal(ks[1], (L, d, H, dh), d, dtype),
            "wk": normal(ks[2], (L, d, G, dh), d, dtype),
            "wv": normal(ks[3], (L, d, G, dh), d, dtype),
            "wo": normal(ks[4], (L, H, dh, d), H * dh, dtype),
        },
        "mlp": {
            "w_in": normal(ks[5], (L, d, 2 * ff), d, dtype),
            "w_out": normal(ks[6], (L, ff, d), ff, dtype),
        },
    }
    return {"embed": {"tok": tok}, "groups": ((layer,),), "final_norm": None}


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (n, T, heads, dh); position t rotates pair (i, i + dh/2) by t / theta^(2i/dh)."""
    T, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]  # (T, dh/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _block(conf: dict, mode: str, x: jax.Array, w: dict) -> jax.Array:
    d, H, G, dh, ff, V, L = dims(conf)
    T = x.shape[1]
    h = layernorm(x, LN_EPS)
    q = _rope(mm("ntd,dhk->nthk", h, w["attn"]["wq"], mode), conf["rope_theta"])
    k = _rope(mm("ntd,dgk->ntgk", h, w["attn"]["wk"], mode), conf["rope_theta"])
    v = mm("ntd,dgk->ntgk", h, w["attn"]["wv"], mode)
    k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
    s = mm("nthk,nshk->nhts", q, k, mode) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("nhts,nshk->nthk", p, v, mode)
    x = x + mm("nthk,hkd->ntd", o, w["attn"]["wo"], mode)
    h = layernorm(x, LN_EPS)
    a = mm("ntd,df->ntf", h, w["mlp"]["w_in"], mode)
    gate, up = a[..., :ff], a[..., ff:]
    return x + mm("ntf,fd->ntd", jax.nn.silu(gate) * up, w["mlp"]["w_out"], mode)


def _trunk(conf: dict, params: dict, tokens: jax.Array, mode: str, remat: bool) -> jax.Array:
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    body = lambda x, w: (_block(conf, mode, x, w), None)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["groups"][0][0])
    return layernorm(x, LN_EPS)


def logits(conf: dict, params: dict, tokens: jax.Array, start: int, mode: str = "f32"):
    """Logits (n, T - start, vocab_size) at positions start..T-1 of ``tokens`` (n, T)."""
    x = _trunk(conf, params, tokens, mode, remat=False)[:, start:]
    return mm("ntd,vd->ntv", x, params["embed"]["tok"][: conf["vocab_size"]], mode)


def loss(conf: dict, params: dict, tokens: jax.Array, mode: str = "f32") -> jax.Array:
    """Mean next-token cross-entropy over every position of ``tokens`` (B, S)."""
    x = _trunk(conf, params, tokens, mode, remat=True)[:, :-1]
    lg = mm("ntd,vd->ntv", x, params["embed"]["tok"][: conf["vocab_size"]], mode)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
