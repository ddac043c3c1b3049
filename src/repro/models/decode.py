"""Serving entry points: cache init, prefill, and single-token decode.

Caches mirror the parameter structure — one pytree per layer group with
leaves stacked over the group's ``count``.  The decode step scans the
layers with ``lax.scan(body, (x, cache_stack), (layer_index, param_stack))``:
the stacked caches ride in the carry and are never copied whole.  Each
layer reads its slice of the stack, and what the step changes is written
in place, by layer kind: a KV cache is only read inside the scan, and each
layer's new k/v (one token) is written into the donated stack after it; a
recurrent state (RWKV, RG-LRU) is rewritten in its layer's slice of the
carry; cross K/V is never written.

Cache contents by layer kind:
  ATTN   — global KV cache, capacity = max sequence length.
  LOCAL  — ring-buffer KV cache, capacity = window (O(1) in context length:
           this is what makes ``long_500k`` run for SWA / hybrid archs).
  XATTN  — precomputed cross K/V over frontend embeddings.
  ATTNX  — self KV cache + cross K/V (whisper decoder).
  RWKV   — WKV state (B,H,K,V) + token-shift states (O(1)).
  RGLRU  — recurrence state (B,W) + conv tail (O(1)).

Both programs name their parts with ``jax.named_scope``, which the compiler
keeps in each op's ``op_name`` and the device profiler shows: the root
``prefill`` or ``decode``, then ``embed``, ``layers`` (the layer scan; its
own stacking and carry of the caches is named by nothing deeper), one scope
per sublayer (``attn`` with ``kv_cache`` nested, ``xattn``, ``mlp``/``moe``,
``time_mix`` with ``wkv`` nested, ``channel_mix``, ``recurrent``), and
``lm_head``.  Scopes are metadata: they add no op to the programs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN,
    ATTNX,
    LOCAL,
    ModelConfig,
    RGLRU,
    RWKV,
    XATTN,
)
from repro.models import attention as attn
from repro.models import griffin, moe, rwkv
from repro.models.common import apply_norm, dtype_of, mlp_apply, unembed
from repro.models.transformer import (
    DistContext,
    _constrain,
    _dp_spec,
    _embed_tokens,
    _ffn,
    _positions_embed,
    _run_encoder,
)


# --------------------------------------------------------------------------
# Cache init.
# --------------------------------------------------------------------------

def _layer_cache(cfg: ModelConfig, kind: str, batch: int, capacity: int) -> dict:
    G, dh = cfg.n_kv_heads, cfg.head_dim_
    T = max(cfg.frontend_tokens, 1)
    dt = dtype_of(cfg)
    if kind == ATTN:
        return attn.init_kv_cache(cfg, batch, capacity)
    if kind == LOCAL:
        return attn.init_kv_cache(cfg, batch, attn.cache_capacity(cfg.window, capacity))
    if kind == XATTN:
        return {
            "ck": jnp.zeros((batch, T, G, dh), dt),
            "cv": jnp.zeros((batch, T, G, dh), dt),
        }
    if kind == ATTNX:
        return {
            "kv": attn.init_kv_cache(cfg, batch, capacity),
            "ck": jnp.zeros((batch, T, G, dh), dt),
            "cv": jnp.zeros((batch, T, G, dh), dt),
        }
    if kind == RWKV:
        return rwkv.init_rwkv_cache(cfg, batch)
    if kind == RGLRU:
        return griffin.init_rglru_cache(cfg, batch)
    raise ValueError(kind)


def init_caches(cfg: ModelConfig, batch: int, capacity: int):
    """Zero caches for every group, stacked over the group's count."""
    groups = []
    for g in cfg.groups:
        single = tuple(_layer_cache(cfg, kind, batch, capacity) for kind in g.pattern)
        stacked = jax.tree.map(
            lambda a: jnp.tile(a, (g.count,) + (1,) * a.ndim), single
        )
        groups.append(stacked)
    return tuple(groups)


# --------------------------------------------------------------------------
# Prefill: full forward that also builds caches.
# --------------------------------------------------------------------------

def _prefill_layer(
    cfg: ModelConfig,
    kind: str,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    enc: Optional[jax.Array],
    capacity: int,
    dist: Optional[DistContext],
) -> Tuple[jax.Array, dict]:
    if kind in (ATTN, LOCAL):
        window = cfg.window if kind == LOCAL else 0
        with jax.named_scope("attn"):
            h = apply_norm(cfg, x, p["ln1"])
            q, k, v = attn.qkv_proj(cfg, p["attn"], h, positions)
            cap = capacity if kind == ATTN else attn.cache_capacity(cfg.window, capacity)
            cache = attn.cache_from_kv(k, v, positions, cap)
            o = attn.attend(cfg, q, k, v, positions, positions, window=window)
            a = attn.out_proj(p["attn"], o)
            if cfg.post_norms:
                a = apply_norm(cfg, a, p["post_ln1"])
            x = x + a
        x, _ = _ffn(cfg, p, x, dist)
        return x, cache
    if kind == XATTN:
        with jax.named_scope("xattn"):
            ck, cv = attn.cross_kv(cfg, p["xattn"], enc)
            h = apply_norm(cfg, x, p["ln1"])
            a = attn.cross_attention(cfg, p["xattn"], h, (ck, cv))
            x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * a
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * mlp_apply(cfg, p["mlp"], h)
        return x, {"ck": ck, "cv": cv}
    if kind == ATTNX:
        with jax.named_scope("attn"):
            h = apply_norm(cfg, x, p["ln1"])
            q, k, v = attn.qkv_proj(cfg, p["attn"], h, positions)
            kv = attn.cache_from_kv(k, v, positions, capacity)
            o = attn.attend(cfg, q, k, v, positions, positions)
            x = x + attn.out_proj(p["attn"], o)
        with jax.named_scope("xattn"):
            ck, cv = attn.cross_kv(cfg, p["xattn"], enc)
            h = apply_norm(cfg, x, p["ln_x"])
            x = x + attn.cross_attention(cfg, p["xattn"], h, (ck, cv))
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + mlp_apply(cfg, p["mlp"], h)
        return x, {"kv": kv, "ck": ck, "cv": cv}
    if kind == RWKV:
        with jax.named_scope("time_mix"):
            h = apply_norm(cfg, x, p["ln1"])
            y, state = rwkv.rwkv_time_mix_prefill(cfg, p["tm_cm"], h)
            x = x + y
        with jax.named_scope("channel_mix"):
            h2 = apply_norm(cfg, x, p["ln2"])
            x = x + rwkv.rwkv_channel_mix(cfg, p["tm_cm"], h2)
        cache = {"state": state, "tm_shift": h[:, -1], "cm_shift": h2[:, -1]}
        return x, cache
    if kind == RGLRU:
        with jax.named_scope("recurrent"):
            h = apply_norm(cfg, x, p["ln1"])
            y, cache = griffin.rglru_block_prefill(cfg, p["rec"], h)
            x = x + y
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + mlp_apply(cfg, p["mlp"], h)
        return x, cache
    raise ValueError(kind)


@jax.named_scope("prefill")
def prefill(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S)
    *,
    frontend: Optional[jax.Array] = None,
    capacity: Optional[int] = None,
    dist: Optional[DistContext] = None,
) -> Tuple[jax.Array, tuple]:
    """Returns (logits_last (B, V), caches)."""
    B, S = tokens.shape
    capacity = capacity or S
    positions = jnp.arange(S, dtype=jnp.int32)
    dp_spec = _dp_spec(dist, B)

    enc = None
    if cfg.encoder_layers:
        enc = _run_encoder(cfg, params, frontend)
    elif cfg.family == "vlm":
        enc = frontend

    with jax.named_scope("embed"):
        x = _embed_tokens(cfg, params, tokens)
        x = _positions_embed(cfg, params, x, positions)
    if dist:
        x = _constrain(x, dist, dp_spec)

    caches = []
    for group, gp in zip(cfg.groups, params["groups"]):

        def block(x, p_block, _group=group):
            outs = []
            for kind, p in zip(_group.pattern, p_block):
                x, c = _prefill_layer(cfg, kind, p, x, positions, enc, capacity, dist)
                outs.append(c)
            if dist:
                x = _constrain(x, dist, dp_spec)
            return x, tuple(outs)

        with jax.named_scope("layers"):
            x, cache_stack = jax.lax.scan(block, x, gp)
        caches.append(cache_stack)

    with jax.named_scope("lm_head"):
        x = apply_norm(cfg, x, params["final_norm"])
        logits = unembed(cfg, params["embed"], x[:, -1])
    return logits, tuple(caches)


# --------------------------------------------------------------------------
# Decode: one token against the caches.
# --------------------------------------------------------------------------

def _layer_of(stack, i: jax.Array):
    """Layer ``i`` of a cache stacked over its group's count."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def _with_layer(stack, i: jax.Array, layer):
    """``stack`` with layer ``i`` replaced by ``layer``, in place."""
    return jax.tree.map(lambda a, l: jax.lax.dynamic_update_index_in_dim(a, l, i, 0), stack, layer)


def _decode_layer(
    cfg: ModelConfig,
    kind: str,
    p: dict,
    x: jax.Array,  # (B, 1, d)
    pos: jax.Array,  # scalar
    stack: dict,  # this kind's cache, stacked over the group's count
    i: jax.Array,  # scalar: the layer's index in the stack
    dist: Optional[DistContext],
) -> Tuple[jax.Array, dict, Optional[tuple]]:
    """Returns (x, the stack, the new token's (k, v) or None).  A recurrent
    state comes back with layer ``i`` rewritten in place; a KV cache and
    cross K/V are only read here, and the new token's k/v are written after
    the layer scan (``_write_tokens``)."""
    if kind in (ATTN, LOCAL):
        with jax.named_scope("attn"):
            h = apply_norm(cfg, x, p["ln1"])
            a, kv = attn.decode_attention(
                cfg, p["attn"], h, pos, stack, i, window=cfg.window if kind == LOCAL else 0
            )
            if cfg.post_norms:
                a = apply_norm(cfg, a, p["post_ln1"])
            x = x + a
        x, _ = _ffn(cfg, p, x, dist)
        return x, stack, kv
    if kind == XATTN:
        cross = _layer_of(stack, i)
        with jax.named_scope("xattn"):
            h = apply_norm(cfg, x, p["ln1"])
            a = attn.cross_attention(cfg, p["xattn"], h, (cross["ck"], cross["cv"]))
            x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * a
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * mlp_apply(cfg, p["mlp"], h)
        return x, stack, None
    if kind == ATTNX:
        with jax.named_scope("attn"):
            h = apply_norm(cfg, x, p["ln1"])
            a, kv = attn.decode_attention(cfg, p["attn"], h, pos, stack["kv"], i, window=0)
            x = x + a
        cross = _layer_of({"ck": stack["ck"], "cv": stack["cv"]}, i)
        with jax.named_scope("xattn"):
            h = apply_norm(cfg, x, p["ln_x"])
            x = x + attn.cross_attention(cfg, p["xattn"], h, (cross["ck"], cross["cv"]))
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + mlp_apply(cfg, p["mlp"], h)
        return x, stack, kv
    if kind == RWKV:
        cache = _layer_of(stack, i)
        with jax.named_scope("time_mix"):
            h = apply_norm(cfg, x, p["ln1"])
            y, cache = rwkv.rwkv_time_mix_decode(cfg, p["tm_cm"], h, cache)
            x = x + y
        with jax.named_scope("channel_mix"):
            h2 = apply_norm(cfg, x, p["ln2"])
            y2, cache = rwkv.rwkv_channel_mix_decode(cfg, p["tm_cm"], h2, cache)
            x = x + y2
        return x, _with_layer(stack, i, cache), None
    if kind == RGLRU:
        cache = _layer_of(stack, i)
        with jax.named_scope("recurrent"):
            h = apply_norm(cfg, x, p["ln1"])
            y, cache = griffin.rglru_block_decode(cfg, p["rec"], h, cache)
            x = x + y
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + mlp_apply(cfg, p["mlp"], h)
        return x, _with_layer(stack, i, cache), None
    raise ValueError(kind)


def _write_tokens(cfg: ModelConfig, kind: str, stack: dict, kv, pos: jax.Array) -> dict:
    """Every layer's new k/v, (count, B, 1, G, dh) each, into the stack."""
    if kind in (ATTN, LOCAL):
        with jax.named_scope("attn"):
            return attn.write_kv(stack, *kv, pos, window=cfg.window if kind == LOCAL else 0)
    if kind == ATTNX:
        with jax.named_scope("attn"):
            return dict(stack, kv=attn.write_kv(stack["kv"], *kv, pos))
    return stack


@jax.named_scope("decode")
def decode_step(
    cfg: ModelConfig,
    params: dict,
    caches: tuple,
    token: jax.Array,  # (B, 1) int32
    pos: jax.Array,  # scalar int32 — absolute position of this token
    *,
    dist: Optional[DistContext] = None,
) -> Tuple[jax.Array, tuple]:
    """Returns (logits (B, V) f32, new_caches)."""
    dp_spec = _dp_spec(dist, token.shape[0])
    with jax.named_scope("embed"):
        x = _embed_tokens(cfg, params, token)
        x = _positions_embed(cfg, params, x, pos[None])
    if dist:
        x = _constrain(x, dist, dp_spec)

    new_caches = []
    for group, gp, gc in zip(cfg.groups, params["groups"], caches):

        def block(carry, inputs, _group=group):
            x, c_block = carry
            i, p_block = inputs
            c_block, tokens = list(c_block), []
            for j, (kind, p) in enumerate(zip(_group.pattern, p_block)):
                x, c_block[j], kv = _decode_layer(cfg, kind, p, x, pos, c_block[j], i, dist)
                tokens.append(kv)
            return (x, tuple(c_block)), tuple(tokens)

        with jax.named_scope("layers"):
            (x, gc), tokens = jax.lax.scan(
                block, (x, gc), (jnp.arange(group.count, dtype=jnp.int32), gp))
            gc = tuple(_write_tokens(cfg, kind, c, kv, pos)
                       for kind, c, kv in zip(group.pattern, gc, tokens))
        new_caches.append(gc)

    with jax.named_scope("lm_head"):
        x = apply_norm(cfg, x, params["final_norm"])
        logits = unembed(cfg, params["embed"], x[:, -1])
    return logits, tuple(new_caches)
