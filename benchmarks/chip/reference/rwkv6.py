"""Plain float32 reference of RWKV-6 "Finch" (Peng et al., arXiv:2404.05892),
and the weights the benchmark draws from the seed.

Each of ``num_hidden_layers`` blocks is a time mix then a channel mix, each
after a LayerNorm (scale and bias) and added to the residual stream:

- time mix: token shift (the previous position's input, zero before the
  first), per-channel interpolation ``x + (x_prev - x) * mu`` for the decay,
  receptance, key, value and gate inputs; data-dependent decay
  ``w_t = exp(-exp(w0 + tanh(x_w A) B))`` through a LoRA of rank
  ``decay_lora_rank``; the WKV
  recurrence per head of size ``head_size``::

      y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)
      S_t = diag(w_t) S_{t-1} + k_t v_t^T

  then a per-head normalisation (GroupNorm with one group per head), times
  ``silu(x_g W_g)``, through ``W_o``;
- channel mix: ``sigmoid(x_r W_r) * (relu(x_k W_k)^2 W_v)`` on token-shifted
  inputs.

A final LayerNorm and an untied head give the logits.  Float32 throughout,
matmuls at ``Precision.HIGHEST``, and the recurrence step by step: the
plainest form, and one the chunked program and kernel do not share.

Departures from the paper, each because the system under test does the same:

- the interpolation weights ``mu`` are static per channel (Finch makes them
  data-dependent through a second LoRA, "ddlerp");
- the decay exponent is clipped to [-8, 8]; the GroupNorm has a scale and no
  bias, with epsilon 64e-5; LayerNorm epsilon is 1e-5;
- there is no LayerNorm straight after the embedding (Finch's ``ln0``);
- weights are random (``weights``), not the released ones.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from refmath import layernorm, mm, normal

LN_EPS = 1e-5
GN_EPS = 64e-5
# widths of the CPU tests (smoke.shrink, test_reference.py)
SMALL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             head_size=16, intermediate_size=128, vocab_size=256)


def dims(conf: dict):
    d, H, K = conf["hidden_size"], conf["num_attention_heads"], conf["head_size"]
    if H * K != d:
        raise ValueError(f"{H} heads of {K} do not make hidden_size {d}")
    return d, H, K, conf["intermediate_size"], conf["vocab_size"], conf["num_hidden_layers"]


def weights(conf: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in the layout the program takes (traceable; jit it)."""
    d, H, K, ff, V, L = dims(conf)
    R = conf["decay_lora_rank"]
    ks = iter(jax.random.split(key, 24))
    f32 = jnp.float32
    u01 = lambda shape: jax.random.uniform(next(ks), shape, f32)

    def ln(n):
        return {"scale": (1.0 + 0.1 * jax.random.normal(next(ks), (n, d), f32)).astype(dtype),
                "bias": (0.1 * jax.random.normal(next(ks), (n, d), f32)).astype(dtype)}

    layer = {
        "ln1": ln(L),
        "ln2": ln(L),
        "tm_cm": {
            "mu": u01((L, 5, d)),
            # decay rates from fast (w ~ 0.07) to slow (w ~ 0.9975) per channel
            "w0": -6.0 + 7.0 * u01((L, d)),
            "decay_A": normal(next(ks), (L, d, R), d, f32),
            "decay_B": normal(next(ks), (L, R, d), R, f32),
            "u": 0.5 * jax.random.normal(next(ks), (L, d), f32),
            "wr": normal(next(ks), (L, d, d), d, dtype),
            "wk": normal(next(ks), (L, d, d), d, dtype),
            "wv": normal(next(ks), (L, d, d), d, dtype),
            "wg": normal(next(ks), (L, d, d), d, dtype),
            "wo": normal(next(ks), (L, d, d), d, dtype),
            "ln_scale": 1.0 + 0.1 * jax.random.normal(next(ks), (L, H, K), f32),
            "cmu": u01((L, 2, d)),
            "cm_k": normal(next(ks), (L, d, ff), d, dtype),
            "cm_v": normal(next(ks), (L, ff, d), ff, dtype),
            "cm_r": normal(next(ks), (L, d, d), d, dtype),
        },
    }
    fin = ln(1)
    return {
        "embed": {"tok": normal(next(ks), (V, d), d, dtype),
                  "head": normal(next(ks), (d, V), d, dtype)},
        "groups": ((layer,),),
        "final_norm": {"scale": fin["scale"][0], "bias": fin["bias"][0]},
    }


def _ln(x, p):
    return layernorm(x, LN_EPS) * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, log_w, u):
    """The recurrence, one position at a time.  r, k, v, log_w: (n, T, H, K)."""
    n, T, H, K = r.shape
    w = jnp.exp(log_w)

    def step(S, inp):
        rt, kt, vt, wt = inp  # (n, H, K)
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("nhk,nhkv->nhv", rt, S + u[None, :, :, None] * kv, precision="highest")
        return wt[..., None] * S + kv, y

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, jnp.zeros((n, H, K, K), jnp.float32), xs)
    return ys.transpose(1, 0, 2, 3)


def _block(conf: dict, mode: str, x: jax.Array, p: dict) -> jax.Array:
    d, H, K, ff, V, L = dims(conf)
    n, T, _ = x.shape
    tm = jax.tree.map(lambda a: a.astype(jnp.float32), p["tm_cm"])
    h = _ln(x, p["ln1"])
    dx = _shift(h) - h
    mw, mr, mk, mv, mg = (h + dx * tm["mu"][i] for i in range(5))
    log_w = -jnp.exp(jnp.clip(
        tm["w0"] + mm("ntr,rd->ntd", jnp.tanh(mm("ntd,dr->ntr", mw, tm["decay_A"], mode)),
                      tm["decay_B"], mode), -8.0, 8.0))
    r = mm("ntd,de->nte", mr, tm["wr"], mode)
    k = mm("ntd,de->nte", mk, tm["wk"], mode)
    v = mm("ntd,de->nte", mv, tm["wv"], mode)
    g = jax.nn.silu(mm("ntd,de->nte", mg, tm["wg"], mode))
    heads = lambda a: a.reshape(n, T, H, K)
    y = _wkv(heads(r), heads(k), heads(v), heads(log_w), tm["u"].reshape(H, K))
    y = layernorm(y, GN_EPS) * tm["ln_scale"]
    x = x + mm("ntd,de->nte", y.reshape(n, T, d) * g, tm["wo"], mode)
    h = _ln(x, p["ln2"])
    dx = _shift(h) - h
    mk, mr = h + dx * tm["cmu"][0], h + dx * tm["cmu"][1]
    kk = jnp.square(jax.nn.relu(mm("ntd,df->ntf", mk, tm["cm_k"], mode)))
    return x + jax.nn.sigmoid(mm("ntd,de->nte", mr, tm["cm_r"], mode)) * mm(
        "ntf,fd->ntd", kk, tm["cm_v"], mode)


def logits(conf: dict, params: dict, tokens: jax.Array, start: int, mode: str = "f32"):
    """Logits (n, T - start, vocab_size) at positions start..T-1 of ``tokens`` (n, T)."""
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda x, p: (_block(conf, mode, x, p), None), x, params["groups"][0][0])
    x = _ln(x[:, start:], params["final_norm"])
    return mm("ntd,dv->ntv", x, params["embed"]["head"], mode)
