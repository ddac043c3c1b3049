"""Int8 gradient compression with error feedback, for the slow (DCN) tier.

The paper's lesson is to reshape slow-tier traffic; quantization is the
orthogonal distributed-optimization trick that shrinks it 4x (f32 -> int8 +
one f32 scale per block).  Error feedback keeps SGD/Adam convergence: the
quantization residual is added back into the next step's gradient, so the
bias telescopes.

``compressed_allreduce_slow`` composes the paper's hierarchical strategy
with compression: reduce-scatter over the fast ICI axes in full precision,
quantize only the 1/k shard that must cross DCN, all-gather int8 over the
pod axis, dequantize + sum, all-gather over ICI.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax

from jax import shard_map
import jax.numpy as jnp

BLOCK = 1024  # per-block scales bound quantization error by max|g|_block/127


def quantize_int8(x: jax.Array, block: int = BLOCK) -> Tuple[jax.Array, jax.Array]:
    """x (f32, any shape) -> (q int8 flat-padded, scales f32)."""
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    blocks = flat.reshape(-1, block).astype(jnp.float32)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0]


def dequantize_int8(q: jax.Array, scale: jax.Array, shape, block: int = BLOCK) -> jax.Array:
    deq = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return deq[:n].reshape(shape)


def quantize_with_feedback(
    g: jax.Array, err: jax.Array, block: int = BLOCK
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Error-feedback quantization: returns (q, scales, new_err)."""
    g_corr = g.astype(jnp.float32) + err
    q, s = quantize_int8(g_corr, block)
    deq = dequantize_int8(q, s, g.shape, block)
    return q, s, g_corr - deq


# --------------------------------------------------------------------------
# shard_map building block (use inside an existing shard_map body).
# --------------------------------------------------------------------------

def compressed_allreduce_slow_inner(
    x: jax.Array,  # this device's contribution, any shape
    slow_axis: str,
    fast_axes: Tuple[str, ...],
    fast_size: int,
    block: int = BLOCK,
) -> jax.Array:
    """Hierarchical all-reduce where only int8(+scales) crosses ``slow_axis``.

    RS(fast, f32) -> quantize shard -> all_gather(slow, int8) -> local sum
    of dequantized contributions -> AG(fast).
    """
    orig_shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % max(fast_size, 1)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = flat
    for a in fast_axes:
        shard = jax.lax.psum_scatter(shard, a, scatter_dimension=0, tiled=True)
    q, s = quantize_int8(shard, block)
    q_all = jax.lax.all_gather(q, slow_axis, axis=0)  # (pods, nblk, block) int8
    s_all = jax.lax.all_gather(s, slow_axis, axis=0)  # (pods, nblk)
    deq = (q_all.astype(jnp.float32) * s_all[..., None]).sum(axis=0)
    shard_sum = deq.reshape(-1)[: shard.size]
    out = shard_sum
    for a in reversed(fast_axes):
        out = jax.lax.all_gather(out, a, axis=0, tiled=True)
    out = out[: flat.size - pad] if pad else out
    return out.reshape(orig_shape)


def compressed_allreduce(
    x: jax.Array,
    mesh,
    slow_axis: str = "pod",
    fast_axes: Sequence[str] = ("data",),
    block: int = BLOCK,
) -> jax.Array:
    """Global-array wrapper: leading dim indexes replicas over
    (slow, *fast) axes (same contract as comms.allreduce)."""
    from jax.sharding import PartitionSpec as P

    fast_axes = tuple(fast_axes)
    all_axes = (slow_axis,) + fast_axes
    k = 1
    for a in all_axes:
        k *= mesh.shape[a]
    if x.shape[0] != k:
        raise ValueError(f"lead dim {x.shape[0]} != replicas {k}")
    fast_size = 1
    for a in fast_axes:
        fast_size *= mesh.shape[a]
    spec = P(all_axes, *([None] * (x.ndim - 1)))

    def body(v):
        return compressed_allreduce_slow_inner(
            v[0], slow_axis, fast_axes, fast_size, block
        )[None]

    fn = shard_map(
        body, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )
    return fn(x)
