"""Plain float32 reference of a few optimizer steps: AdamW with decoupled
weight decay (Loshchilov & Hutter, arXiv:1711.05101), clipping by global norm,
and a linear-warmup cosine schedule, as the configuration's traffic file states
them.

The reference's parameters are float32 on the device, its moments float32
on the host, moved in one layer's slice at a time: a model whose parameters,
gradients and two moments do not fit the chip together in float32 can still
be followed exactly.  Imports nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from refmath import is_stacked, slice_norms


def lr_at(step: int, hp: dict) -> float:
    """Learning rate of optimizer step ``step`` (0-based)."""
    peak, warm, total = hp["lr"], hp["warmup_steps"], hp["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    low = hp["lr_min_ratio"]
    return peak * (low + (1 - low) * 0.5 * (1 + math.cos(math.pi * prog)))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"))
def _update(p, g, m, v, scale, lr, bc1, bc2, *, b1, b2, eps, wd):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p)
    return p, m, v


@functools.partial(jax.jit, donate_argnums=(0,))
def _put(leaf, i, value):
    return jax.lax.dynamic_update_index_in_dim(leaf, value, i, 0)


@jax.jit
def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def train(loss, params, batches, hp: dict, store=None):
    """Follow ``len(batches)`` steps from ``params`` (a float32 tree on the
    device, consumed).  ``loss(params, tokens)`` is the model's loss;
    ``store``, where given, rounds each updated parameter slice to the
    precision the parameters are kept in.

    Returns (losses, first clipped gradient's slice norms, parameters)."""
    vg = jax.jit(jax.value_and_grad(loss))
    kw = dict(b1=hp["b1"], b2=hp["b2"], eps=hp["eps"], wd=hp["weight_decay"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    paths = [p for p, _ in flat]
    leaves = [leaf for _, leaf in flat]
    del params, flat
    moments = {}  # (leaf, slice) -> (m, v) on the host
    losses, first = [], None
    for step, tokens in enumerate(batches):
        value, grads = vg(jax.tree_util.tree_unflatten(treedef, leaves), tokens)
        gnorm = float(_global_norm(grads))
        scale = min(1.0, hp["grad_clip"] / max(gnorm, 1e-9))
        if first is None:
            first = np.asarray(slice_norms(grads)) * scale
        losses.append(float(value))
        lr = lr_at(step, hp)
        bc1, bc2 = 1 - hp["b1"] ** (step + 1), 1 - hp["b2"] ** (step + 1)
        last = step == len(batches) - 1
        for i, g in enumerate(jax.tree.leaves(grads)):
            parts = range(g.shape[0]) if is_stacked(paths[i]) else [None]
            for j in parts:
                p_s = leaves[i] if j is None else leaves[i][j]
                g_s = g if j is None else g[j]
                m, v = moments.get((i, j), (np.zeros(p_s.shape, np.float32),) * 2)
                p_new, m, v = _update(p_s, g_s, m, v, scale, lr, bc1, bc2, **kw)
                if store is not None:
                    p_new = store(p_new)
                leaves[i] = p_new if j is None else _put(leaves[i], j, p_new)
                if not last:
                    moments[(i, j)] = (np.asarray(m), np.asarray(v))
                del m, v
        del grads
    return losses, first, jax.tree_util.tree_unflatten(treedef, leaves)
