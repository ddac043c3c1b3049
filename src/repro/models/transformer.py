"""The composable model: layer groups scanned over stacked parameters.

A model is ``cfg.groups`` — each group a *superblock* (tuple of layer kinds)
repeated ``count`` times via ``lax.scan`` over stacked parameters, keeping
the lowered HLO O(superblock) regardless of depth (essential for the
512-device dry-run).  Supported kinds: ATTN, LOCAL, XATTN (gated cross-attn,
llama-vision), ATTNX (self+cross, whisper decoder), RWKV, RGLRU.

Distribution: ``DistContext`` carries the mesh + axis names.  Dense compute
is plain einsum (GSPMD shards it from the weight shardings declared in
``repro.sharding.specs``); the MoE block drops into an explicit
``shard_map`` all-to-all whose strategy is planner-selected — the paper's
technique as a first-class feature.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (
    ATTN,
    ATTNX,
    LOCAL,
    LayerGroup,
    ModelConfig,
    RGLRU,
    RWKV,
    XATTN,
)
from repro.models import attention as attn
from repro.models import griffin, moe, rwkv
from repro.models.common import (
    apply_norm,
    dtype_of,
    embed_params,
    mlp_apply,
    mlp_params,
    norm_params,
    unembed,
)

AUX_LOSS_COEF = 0.01


@dataclasses.dataclass(frozen=True)
class DistContext:
    """Static distribution context threaded through the model."""

    mesh: Any  # jax.sharding.Mesh
    dp_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    ep_shards: int = 1
    moe_strategy: str = "direct"  # direct | chunked | hierarchical
    a2a_chunks: int = 1
    # mesh axes carrying virtual experts; ("data", "model") is the serving
    # layout (256-way EP, no FSDP gathers) whose dispatch is the paper's
    # two-hop Alltoall case study
    ep_axes: Tuple[str, ...] = ("model",)

    @property
    def ep_size(self) -> int:
        n = 1
        for a in self.ep_axes:
            n *= self.mesh.shape[a]
        return n


def _constrain(x: jax.Array, dist: Optional[DistContext], spec: P) -> jax.Array:
    if dist is None or dist.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(dist.mesh, spec)
    )


def _dp_spec(dist: Optional[DistContext], batch: int) -> P:
    """Batch-sharded spec when the batch divides the DP extent, else
    replicated (long-context decode with batch 1)."""
    if dist is None:
        return P(None, None, None)
    import math

    dp = math.prod(dist.mesh.shape[a] for a in dist.dp_axes)
    return P(dist.dp_axes, None, None) if batch % dp == 0 else P(None, None, None)


# --------------------------------------------------------------------------
# Parameter init.
# --------------------------------------------------------------------------

def _layer_params(cfg: ModelConfig, kind: str, rng: jax.Array, ep_shards: int) -> dict:
    ks = jax.random.split(rng, 8)
    d = cfg.d_model
    p: dict = {"ln1": norm_params(cfg, ks[0], d), "ln2": norm_params(cfg, ks[1], d)}
    if kind in (ATTN, LOCAL):
        p["attn"] = attn.attn_params(cfg, ks[2])
        if cfg.is_moe:
            p["moe"] = moe.moe_params(cfg, ks[3], ep_shards)
        else:
            p["mlp"] = mlp_params(cfg, ks[3])
        if cfg.post_norms:
            p["post_ln1"] = norm_params(cfg, ks[4], d)
            p["post_ln2"] = norm_params(cfg, ks[5], d)
    elif kind == XATTN:  # gated cross-attention layer (llama-vision)
        p["xattn"] = attn.attn_params(cfg, ks[2], kv_input_dim=cfg.frontend_dim or d)
        p["mlp"] = mlp_params(cfg, ks[3])
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["gate_mlp"] = jnp.zeros((), jnp.float32)
    elif kind == ATTNX:  # whisper decoder layer: self + cross + mlp
        p["attn"] = attn.attn_params(cfg, ks[2])
        p["ln_x"] = norm_params(cfg, ks[6], d)
        p["xattn"] = attn.attn_params(cfg, ks[7], kv_input_dim=d)
        p["mlp"] = mlp_params(cfg, ks[3])
    elif kind == RWKV:
        p["tm_cm"] = rwkv.rwkv_params(cfg, ks[2])
    elif kind == RGLRU:
        p["rec"] = griffin.rglru_params(cfg, ks[2])
        p["mlp"] = mlp_params(cfg, ks[3])
    else:
        raise ValueError(kind)
    return p


def _superblock_params(cfg: ModelConfig, group: LayerGroup, rng: jax.Array, ep_shards: int):
    def one(key):
        ks = jax.random.split(key, len(group.pattern))
        return tuple(
            _layer_params(cfg, kind, k, ep_shards)
            for kind, k in zip(group.pattern, ks)
        )

    return jax.vmap(one)(jax.random.split(rng, group.count))


def init_params(cfg: ModelConfig, rng: jax.Array, ep_shards: int = 1) -> dict:
    k_embed, k_groups, k_fin, k_enc = jax.random.split(rng, 4)
    params: dict = {"embed": embed_params(cfg, k_embed)}
    gks = jax.random.split(k_groups, max(len(cfg.groups), 1))
    params["groups"] = tuple(
        _superblock_params(cfg, g, gk, ep_shards) for g, gk in zip(cfg.groups, gks)
    )
    params["final_norm"] = norm_params(cfg, k_fin, cfg.d_model)
    if cfg.encoder_layers:
        enc_cfg = dataclasses.replace(cfg, post_norms=False)

        def enc_one(key):
            ks = jax.random.split(key, 3)
            return {
                "ln1": norm_params(cfg, ks[0], cfg.d_model),
                "attn": attn.attn_params(enc_cfg, ks[1]),
                "ln2": norm_params(cfg, ks[2], cfg.d_model),
                "mlp": mlp_params(cfg, ks[1]),
            }

        params["encoder"] = {
            "layers": jax.vmap(enc_one)(jax.random.split(k_enc, cfg.encoder_layers)),
            "final_norm": norm_params(cfg, k_enc, cfg.d_model),
            "pos": 0.02
            * jax.random.normal(
                k_enc, (max(cfg.frontend_tokens, 1), cfg.d_model), jnp.float32
            ).astype(dtype_of(cfg)),
            # frontend embeddings arrive at frontend_dim; project if needed
        }
    return params


# --------------------------------------------------------------------------
# MoE dispatch (dense on 1 device; shard_map all-to-all when distributed).
# --------------------------------------------------------------------------

def _moe_call(cfg: ModelConfig, p: dict, x: jax.Array, dist: Optional[DistContext]):
    if dist is None or dist.mesh is None:
        return moe.moe_apply_dense(cfg, p, x, ep_shards=max(dist.ep_shards if dist else 1, 1))
    ax = moe.MoEAxis(
        dist.ep_axes,
        dist.ep_size,
        dist.ep_shards,
        axis_sizes=tuple(dist.mesh.shape[a] for a in dist.ep_axes),
    )
    # if an expert axis doubles as a data axis (serving layout), x enters
    # replicated over it; otherwise batch-shard over dp
    dp_clash = any(a in dist.ep_axes for a in dist.dp_axes)
    dp_spec = P(None, None, None) if dp_clash else _dp_spec(dist, x.shape[0])

    def body(xl, router, w_in, w_out):
        y, aux = moe.moe_apply_sharded_inner(
            cfg,
            {"router": router, "w_in": w_in, "w_out": w_out},
            xl,
            ax,
            strategy=dist.moe_strategy,
            a2a_chunks=dist.a2a_chunks,
        )
        # aux is already pmean'd over the expert axis inside; average the
        # remaining data-parallel axes so it is globally replicated.
        return y, jax.lax.pmean(aux, dist.dp_axes)

    fn = shard_map(
        body,
        mesh=dist.mesh,
        in_specs=(
            dp_spec,
            P(None, None),
            P(dist.ep_axes, None, None),
            P(dist.ep_axes, None, None),
        ),
        out_specs=(dp_spec, P()),
        # y is all_gathered over the expert axis (hence replicated), but the
        # static varying-axes checker cannot infer that through all_gather.
        check_vma=False,
    )
    return fn(x, p["router"], p["w_in"], p["w_out"])


# --------------------------------------------------------------------------
# Layer application (full sequence).
# --------------------------------------------------------------------------

def _apply_layer_full(
    cfg: ModelConfig,
    kind: str,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    enc: Optional[jax.Array],
    dist: Optional[DistContext],
) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in (ATTN, LOCAL):
        with jax.named_scope("attn"):
            h = apply_norm(cfg, x, p["ln1"])
            a = attn.self_attention(
                cfg, p["attn"], h, positions, window=cfg.window if kind == LOCAL else 0
            )
            if cfg.post_norms:
                a = apply_norm(cfg, a, p["post_ln1"])
            x = x + a
        x, aux = _ffn(cfg, p, x, dist)
    elif kind == XATTN:
        with jax.named_scope("xattn"):
            h = apply_norm(cfg, x, p["ln1"])
            kv = attn.cross_kv(cfg, p["xattn"], enc)
            a = attn.cross_attention(cfg, p["xattn"], h, kv)
            x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * a
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * mlp_apply(cfg, p["mlp"], h)
    elif kind == ATTNX:
        with jax.named_scope("attn"):
            h = apply_norm(cfg, x, p["ln1"])
            x = x + attn.self_attention(cfg, p["attn"], h, positions, window=0)
        with jax.named_scope("xattn"):
            h = apply_norm(cfg, x, p["ln_x"])
            kv = attn.cross_kv(cfg, p["xattn"], enc)
            x = x + attn.cross_attention(cfg, p["xattn"], h, kv)
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + mlp_apply(cfg, p["mlp"], h)
    elif kind == RWKV:
        with jax.named_scope("time_mix"):
            h = apply_norm(cfg, x, p["ln1"])
            x = x + rwkv.rwkv_time_mix(cfg, p["tm_cm"], h)
        with jax.named_scope("channel_mix"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + rwkv.rwkv_channel_mix(cfg, p["tm_cm"], h)
    elif kind == RGLRU:
        with jax.named_scope("recurrent"):
            h = apply_norm(cfg, x, p["ln1"])
            x = x + griffin.rglru_block(cfg, p["rec"], h)
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, x, p["ln2"])
            x = x + mlp_apply(cfg, p["mlp"], h)
    else:
        raise ValueError(kind)
    return x, aux


def _ffn(
    cfg: ModelConfig, p: dict, x: jax.Array, dist: Optional[DistContext]
) -> Tuple[jax.Array, jax.Array]:
    """Feed-forward sublayer of an attention layer (norm, MLP or MoE,
    residual), scoped ``mlp`` or ``moe``.  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("moe" if cfg.is_moe else "mlp"):
        h = apply_norm(cfg, x, p["ln2"])
        if cfg.is_moe:
            m, aux = _moe_call(cfg, p["moe"], h, dist)
        else:
            m = mlp_apply(cfg, p["mlp"], h)
        if cfg.post_norms:
            m = apply_norm(cfg, m, p["post_ln2"])
        return x + m, aux


# --------------------------------------------------------------------------
# Encoder (whisper) — bidirectional attention over frontend embeddings.
# --------------------------------------------------------------------------

def _run_encoder(cfg: ModelConfig, params: dict, frontend: jax.Array) -> jax.Array:
    enc_p = params["encoder"]
    T = frontend.shape[1]
    x = frontend + enc_p["pos"][None, :T]
    positions = jnp.arange(T, dtype=jnp.int32)

    def block(x, p):
        h = apply_norm(cfg, x, p["ln1"])
        x = x + attn.self_attention(cfg, p["attn"], h, positions, causal=False)
        h = apply_norm(cfg, x, p["ln2"])
        x = x + mlp_apply(cfg, p["mlp"], h)
        return x, None

    x, _ = jax.lax.scan(block, x, enc_p["layers"])
    return apply_norm(cfg, x, enc_p["final_norm"])


def _embed_tokens(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    x = params["embed"]["tok"][tokens]
    if "gemma" in cfg.name:  # gemma-family embedding scaling
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    return x


def _positions_embed(cfg, params, x, positions):
    if cfg.pos == "learned":
        x = x + params["embed"]["pos"][positions]
    return x


# --------------------------------------------------------------------------
# Forward (train / full sequence).
# --------------------------------------------------------------------------

def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S) int32
    *,
    frontend: Optional[jax.Array] = None,  # (B, T, frontend_dim) stub embeds
    dist: Optional[DistContext] = None,
    remat: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B, S, V) f32, aux_loss scalar)."""
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    dp_spec = _dp_spec(dist, B)

    enc = None
    if cfg.encoder_layers:
        enc = _run_encoder(cfg, params, frontend)
    elif cfg.family == "vlm":
        enc = frontend  # raw patch embeddings; XATTN projects K/V from them

    with jax.named_scope("embed"):
        x = _embed_tokens(cfg, params, tokens)
        x = _positions_embed(cfg, params, x, positions)
    x = _constrain(x, dist, dp_spec) if dist else x

    aux_total = jnp.zeros((), jnp.float32)
    for group, gp in zip(cfg.groups, params["groups"]):

        def block(carry, p_block, _group=group):
            x, aux = carry
            for kind, p in zip(_group.pattern, p_block):
                x, a = _apply_layer_full(cfg, kind, p, x, positions, enc, dist)
                aux = aux + a
            if dist:
                x = _constrain(x, dist, dp_spec)
            return (x, aux), None

        if remat in (True, "block"):
            body = jax.checkpoint(block)
        elif remat == "dots":
            body = jax.checkpoint(
                block,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        else:
            body = block
        with jax.named_scope("layers"):
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), gp)

    with jax.named_scope("lm_head"):
        x = apply_norm(cfg, x, params["final_norm"])
        logits = unembed(cfg, params["embed"], x)
    return logits, aux_total * AUX_LOSS_COEF
