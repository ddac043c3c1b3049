"""All-to-all strategies (the paper's §VI case study on the TPU target).

Contract: ``x`` has shape (k, k, *payload) where k = product of the
participating axes' sizes; ``x[i, j]`` is the block rank i sends to rank j.
Output ``out[i, j] = x[j, i]`` — i.e. rank i ends up holding what everyone
sent to it (standard all-to-all), laid out as a global array.

* ``direct``       — one jax.lax.all_to_all over the flattened axes
                     ("CUDA-aware" analogue: every pair exchanges directly;
                     message count per rank = k-1).
* ``hierarchical`` — two-hop: all-to-all over the *inner* (fast/ICI) axis
                     bucketing by outer destination, then all-to-all over the
                     *outer* (slow/DCN) axis with all inner ranks injecting
                     concurrently (3-step + Dup-Devptr analogue: the slow
                     tier sees fewer, better-parallelized transfers; per-rank
                     slow-tier message count drops from k-1 to k_outer-1).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _mesh_size(mesh: Mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# --------------------------------------------------------------------------
# Inner bodies: local view is x_loc (k, *payload) = blocks this rank sends.
# --------------------------------------------------------------------------

def alltoall_direct_inner(x_loc: jax.Array, axes: Tuple[str, ...]) -> jax.Array:
    """x_loc: (k, *payload) send blocks -> (k, *payload) received blocks."""
    return jax.lax.all_to_all(x_loc, axes, split_axis=0, concat_axis=0, tiled=False)


def alltoall_hier_inner(
    x_loc: jax.Array, outer_axis: str, inner_axis: str, outer_size: int, inner_size: int
) -> jax.Array:
    """Two-hop all-to-all.

    Let rank = (o, i) with o over outer_axis (size O), i over inner_axis
    (size I), destination d = (o', i').  x_loc is ordered [d] = [o' * I + i'].

    Hop 1 (fast tier): exchange over inner_axis so that, within each outer
    group, peer i' collects every local rank's blocks destined to
    inner-coordinate i' — i.e. after hop 1, rank (o, i) holds blocks
    [src_i, o'] each of which must go to rank (o', i).

    Hop 2 (slow tier): exchange over outer_axis on the o' dimension.  Every
    (o, i) injects concurrently — all hosts drive the DCN (Dup-Devptr).
    """
    k, *payload = x_loc.shape
    assert k == outer_size * inner_size, (k, outer_size, inner_size)
    # [o', i', *payload] -> hop1 over i' (split inner destination coordinate)
    blocks = jnp.reshape(x_loc, (outer_size, inner_size) + tuple(payload))
    # all_to_all over inner_axis, splitting axis 1 (i'), concatenating the
    # source-inner coordinate as a new leading axis (tiled=False inserts it
    # in place of the split axis).
    hop1 = jax.lax.all_to_all(blocks, inner_axis, split_axis=1, concat_axis=1, tiled=True)
    # hop1: (o', src_i_blocks...) — with tiled=True shape stays (O, I, ...):
    # position [o', s] = block from inner-source s destined (o', my_i).
    # hop2 over outer_axis, splitting o'.
    hop2 = jax.lax.all_to_all(hop1, outer_axis, split_axis=0, concat_axis=0, tiled=True)
    # hop2: (src_o, src_i, *payload) = blocks from global source (src_o,
    # src_i) destined to me.  Flatten back to (k, *payload).
    return jnp.reshape(hop2, (k,) + tuple(payload))


# --------------------------------------------------------------------------
# Global wrappers.
# --------------------------------------------------------------------------

def _wrap(body, mesh: Mesh, axes: Tuple[str, ...], x: jax.Array):
    k = _mesh_size(mesh, axes)
    if x.shape[0] != k or x.shape[1] != k:
        raise ValueError(f"alltoall expects (k, k, *payload) with k={k}, got {x.shape}")
    spec = P(axes, *([None] * (x.ndim - 1)))

    def local(v):  # v: (1, k, *payload)
        return body(v[0])[None]

    fn = shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
    return fn(x)


def alltoall_direct(x: jax.Array, mesh: Mesh, axes: Sequence[str]) -> jax.Array:
    axes = tuple(axes)
    return _wrap(functools.partial(alltoall_direct_inner, axes=axes), mesh, axes, x)


def alltoall_hierarchical(
    x: jax.Array, mesh: Mesh, outer_axis: str, inner_axis: str
) -> jax.Array:
    axes = (outer_axis, inner_axis)
    return _wrap(
        functools.partial(
            alltoall_hier_inner,
            outer_axis=outer_axis,
            inner_axis=inner_axis,
            outer_size=mesh.shape[outer_axis],
            inner_size=mesh.shape[inner_axis],
        ),
        mesh,
        axes,
        x,
    )


def auto_alltoall_strategy(
    x: jax.Array, mesh: Mesh, axes: Sequence[str]
) -> str:
    """Model-driven strategy pick for :func:`alltoall` — consults
    :mod:`repro.comms.autotune` (event-engine schedule search against the
    active machine, closed-form cross-pod plan as fallback) with this
    mesh's shape and the per-pair block size.

    Per-call affordable: repeat consultations for the same (machine, mesh,
    payload-bucket) hit the autotune plan cache instead of re-running the
    schedule search, so MoE dispatch can re-select per step as routed token
    counts shift the payload across bucket boundaries."""
    from repro.comms.autotune import select_alltoall_strategy

    axes = tuple(axes)
    k = _mesh_size(mesh, axes)
    block_bytes = float(x.size // max(k * k, 1)) * x.dtype.itemsize
    # only the participating axes: other mesh axes would inflate the modeled
    # per-pod chip count and price the wrong machine
    shape = {a: mesh.shape[a] for a in axes}
    return select_alltoall_strategy(
        shape, block_bytes, n_msgs=max(k - 1, 1),
        crosses_pod=("pod" in axes and len(axes) == 2),
    )


def alltoall(
    x: jax.Array,
    mesh: Mesh,
    axes: Sequence[str],
    strategy: str = "direct",
) -> jax.Array:
    """Strategy-dispatched all-to-all over the given mesh axes.

    ``strategy="auto"`` asks the performance models (schedule search with
    closed-form fallback, see :func:`auto_alltoall_strategy`)."""
    axes = tuple(axes)
    if strategy == "auto":
        strategy = auto_alltoall_strategy(x, mesh, axes)
    if strategy == "direct" or len(axes) == 1:
        return alltoall_direct(x, mesh, axes)
    if strategy == "hierarchical":
        if len(axes) != 2:
            raise ValueError("hierarchical alltoall needs (outer, inner) axes")
        return alltoall_hierarchical(x, mesh, axes[0], axes[1])
    raise ValueError(f"unknown alltoall strategy {strategy!r}")
