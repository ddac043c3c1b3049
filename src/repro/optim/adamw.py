"""AdamW with decoupled weight decay and global-norm clipping.

Optimizer state shards exactly like the parameters (same pytree structure,
same sharding specs applied by the launcher), so FSDP splits moments too —
ZeRO-style.  Moments are f32 regardless of param dtype (bf16-safe)."""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array  # scalar int32
    mu: Any  # first moment, f32, params-shaped
    nu: Any  # second moment, f32, params-shaped


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def init_state(params) -> AdamWState:
    f32_zeros = lambda p: jnp.zeros_like(p, jnp.float32)  # keeps p's sharding
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree.map(f32_zeros, params),
        nu=jax.tree.map(f32_zeros, params),
    )


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    )


def clip_by_global_norm(grads, max_norm: float) -> Tuple[Any, jax.Array]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), norm


def apply_updates(
    cfg: AdamWConfig,
    params,
    grads,
    state: AdamWState,
    lr: Optional[jax.Array] = None,
) -> Tuple[Any, AdamWState]:
    """One AdamW step.  ``lr`` overrides cfg.lr (schedule hook)."""
    lr = cfg.lr if lr is None else lr
    step = state.step + 1
    bc1 = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m2 = cfg.b1 * m + (1.0 - cfg.b1) * gf
        v2 = cfg.b2 * v + (1.0 - cfg.b2) * jnp.square(gf)
        mhat = m2 / bc1
        vhat = v2 / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m2, v2

    p_flat, treedef = jax.tree.flatten(params)
    g_flat = jax.tree.leaves(grads)
    m_flat = jax.tree.leaves(state.mu)
    v_flat = jax.tree.leaves(state.nu)
    res = [upd(p, g, m, v) for p, g, m, v in zip(p_flat, g_flat, m_flat, v_flat)]
    new_params = jax.tree.unflatten(treedef, [r[0] for r in res])
    new_mu = jax.tree.unflatten(treedef, [r[1] for r in res])
    new_nu = jax.tree.unflatten(treedef, [r[2] for r in res])
    return new_params, AdamWState(step=step, mu=new_mu, nu=new_nu)
