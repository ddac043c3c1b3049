"""Point-to-point patterns built on collective_permute (ppermute)."""
from __future__ import annotations

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ring_shift(x: jax.Array, mesh: Mesh, axis: str, shift: int = 1) -> jax.Array:
    """Cyclically shift per-device blocks (lead dim = axis size) by ``shift``
    positions around the ring: out[(i+shift) % k] = x[i]."""
    k = mesh.shape[axis]
    if x.shape[0] != k:
        raise ValueError(f"ring_shift expects lead dim {k}, got {x.shape}")
    perm = [(i, (i + shift) % k) for i in range(k)]
    spec = P((axis,), *([None] * (x.ndim - 1)))

    def body(v):
        return jax.lax.ppermute(v, axis, perm)

    fn = shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)
    return fn(x)


def halo_exchange(x: jax.Array, mesh: Mesh, axis: str, halo: int) -> jax.Array:
    """1-D halo exchange of a spatially-sharded array (stencil pattern, the
    paper's motivating application class).

    ``x``: (k, n, *feat) — k shards of a length k*n sequence.  Returns
    (k, n + 2*halo, *feat) with neighbour halos attached (zero at edges of
    the ring seam — callers wanting periodic BCs keep the wrap).
    """
    k = mesh.shape[axis]
    if x.shape[0] != k:
        raise ValueError(f"halo_exchange expects lead dim {k}, got {x.shape}")
    fwd = [(i, (i + 1) % k) for i in range(k)]
    bwd = [(i, (i - 1) % k) for i in range(k)]
    spec = P((axis,), *([None] * (x.ndim - 1)))

    def body(v):
        blk = v[0]  # (n, *feat)
        right_edge = blk[-halo:]
        left_edge = blk[:halo]
        from_left = jax.lax.ppermute(right_edge, axis, fwd)  # my left halo
        from_right = jax.lax.ppermute(left_edge, axis, bwd)  # my right halo
        return jnp.concatenate([from_left, blk, from_right], axis=0)[None]

    fn = shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)
    return fn(x)
